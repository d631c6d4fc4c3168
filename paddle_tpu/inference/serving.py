"""Continuous-batching serving loop over the paged KV cache.

Role of the reference's production decode service: the paged cache-KV
branch of `fused_multi_transformer_op.cu.h` (+ `block_multi_head_
attention_kernel.cu`) driven by a request scheduler behind
`analysis_predictor.h:100`.  TPU-native shape:

* ONE compiled decode step for the whole engine, regardless of batch
  mix: fixed `max_batch` slots, a shared physical block pool per layer,
  per-slot block tables and seq_lens as device inputs.  Admissions,
  evictions, and block allocation are HOST-side bookkeeping between
  compiled steps (exactly where serving schedulers live), so joining or
  finishing a sequence never recompiles anything.
* Admission runs a compiled prefill program (cached per padded prompt
  bucket) that writes the prompt's K/V into the new slot's blocks
  through the SAME pools and returns the last real token's logits.
* Free slots ride through the decode program as seq_len-0 rows over an
  all-zero table row (block 0 is the reserved pad block): the paged
  kernels store and copy nothing for them (a latent cache's row write
  lands in the pad block) and their attention output is ignored, so
  occupancy changes cost nothing.
* Sampling happens ON DEVICE inside the compiled k-step tick (the seat
  of the reference's fused top-p path in
  `fused_multi_transformer_op.cu.h`): per-slot temperature/top-k/top-p/
  do_sample masks and PRNG seeds are device INPUTS, so changing the
  sampling mix never recompiles anything and sampled requests amortize
  the host round trip over the same k steps greedy ones do.  The
  host-side per-row sampler survives behind
  ``FLAGS_serving_device_sampling=0`` (it demotes ticks to k=1).
* The serve loop keeps one tick in flight.  ``run()`` (until the engine
  is empty) and ``serve_forever()`` (until its stop event; the loop
  behind every front end, replica and benchmark cell) drive ONE cycle,
  `_cycle`: tick t+1's compiled step is dispatched — feeding tick t's
  on-device last-token handle straight back in — BEFORE tick t is
  harvested, so device compute overlaps host detokenize/bookkeeping.
  JAX async dispatch makes this a reordering plus one in-flight handle,
  not a thread; an EOS discovered at harvest simply wastes the
  already-dispatched step (the block-budget clamp keeps the overrun
  inside the admission reservation).  A chained dispatch skips the
  boundary schedule, the only place admissions, cancellations, sheds
  and evictions happen, so `_can_overlap` refuses the chain — the next
  harvest is then followed by a REAL boundary — whenever the engine's
  own state says a boundary has work: a request waits, a running one
  was cancelled or finished or is on its last budgeted tick, the tick
  kind would switch, a drain was requested or the loop's stop event is
  set.  Nothing waits more than the one tick in flight at its arrival.
  ``FLAGS_serving_overlap=0`` is the synchronous cycle (dispatch,
  harvest, in a row) the bit-equality tests compare against; `step()`
  is always synchronous.

Block accounting reserves the worst case (prompt + max_new_tokens) at
admission, so a running sequence can never hit pool exhaustion
mid-flight (no preemption needed — the reference scheduler's "no-evict"
configuration).

Scale-out (ISSUE 9): ``FLAGS_serving_tp_degree`` rebuilds every program
as a ``shard_map`` over a 'tp' mesh axis — weights column-parallel, KV
pools sharded along the head axis, scheduler state replicated (the
rank-0 broadcast) — with decode streams BIT-identical to degree 1
(`inference/tp.py` has the no-split-reductions layout contract).
``FLAGS_serving_prefix_cache`` adds refcounted prompt-prefix reuse over
the block table: a resident prefix is a pointer copy at admission, the
suffix runs a chunked prefill program, shared blocks copy-on-write when
the last prompt token must be recomputed, and index eviction under pool
pressure frees only orphaned blocks (`inference/prefix_cache.py`).

Speculative + quantized serving (ISSUE 10, extended by ISSUE 13):
``FLAGS_serving_spec_decode`` adds the spec tick — draft tokens for
every slot judged by the target in a single `PagedChunkView` chunk
verify forward, per-slot accept masks emitting 1..k tokens LOSSLESSLY
(greedy bit-identical to the plain engine; seeded sampling corrected
by rejection sampling — `inference/speculative.py`).  The proposal
source is ``FLAGS_serving_spec_draft``: ``model`` runs a draft model's
k-step scan over its own pools behind the SAME block table (prefix
sharing, CoW and refcounts cover both models); ``ngram`` proposes from
a per-request host-side suffix table (`inference/drafting.py`) and
feeds the proposals in as DEVICE INPUTS — no draft model, pools, or
prefill at all.  Eligibility is PER SLOT: each slot carries an emit
cap ``min(k, remaining budget)`` into the program, so a short-budget
slot no longer demotes the whole tick to the plain path — it just
emits up to its cap (budget accounting refunds per slot at harvest).
``FLAGS_serving_spec_adaptive`` steps k through the
``FLAGS_serving_spec_k_ladder`` rungs at tick boundaries, driven by
the live acceptance-rate EWMA; every rung's program is enumerated into
the warmup grid, so adaptation never compiles under traffic.
``FLAGS_serving_quant=int8|fp8`` snapshots the matmul weights
per-channel at construction and dequantizes in-trace
(`inference/quant.py`): ~4x less fp32 weight memory on device, bounded
logit deviation (per-mode budget), bit-exact across TP degrees.

Continuous batching (ISSUE 11): ``FLAGS_serving_prefill_chunk`` makes
prefill INCREMENTAL — an arriving prompt of any length is absorbed as
bounded-size chunks of the suffix-prefill program (one per ladder
bucket, ``start``/length traced scalars — zero new program shapes),
interleaved between decode ticks by a per-tick scheduler that budgets
each boundary as "one decode tick + up to
``FLAGS_serving_prefill_chunks_per_tick`` chunk(s)".  Running streams'
inter-token gap is bounded by one chunk + one tick regardless of
arriving prompt length, and the chunked streams are BIT-identical to
monolithic prefill (same `PagedChunkView` writes, same offset causal
mask).  A mid-prefill slot keeps its table row SHADOWED on the request
(the engine row stays zero) so overlapping decode ticks stay inert for
it.  The scheduler is also SLO-aware: ``FLAGS_serving_slo_shed``
rejects (reason=slo_shed) the newest lowest-priority waiting requests
while the live TTFT/TPOT p99 sketches breach their targets and the
queue is past ``FLAGS_serving_shed_queue_depth``; `Request(priority=)`
orders admission.  ``FLAGS_serving_http_port`` exposes the engine as a
minimal streaming frontend: ``POST /generate`` answers a Server-Sent
Events token stream (`observability/http.py`), with client disconnect
and timeout propagating to `Request.cancel()` -> slot eviction and
block release at the next boundary.

Crash-only serving (ISSUE 15): the tick loop is supervised.  A
dispatch/harvest exception no longer kills ``run()``/``serve_forever``
— transient RuntimeError dispatches retry in place
(``FLAGS_serving_dispatch_retries``, the shared io_retry backoff), an
admission-stage failure strikes the REQUEST (two strikes — its program
raised, or its prefill logits went non-finite under the flight-recorder
watchdog — and it is rejected ``reason=poisoned`` instead of re-crashing
every boundary), and an unattributable tick failure evicts exactly the
implicated slots ``outcome=error`` with every block released through
the single ``_alloc/_ref/_release_block`` path (blocksan stays green)
while the other slots' streams continue bit-identically.  A harvest
that never materializes (hung ``block_until_ready``) is caught by the
tick watchdog (``FLAGS_serving_tick_timeout_s``) and failed like any
other tick error.  ``drain()`` (SIGTERM under ``serve_forever``, or
``POST /drain``) is the graceful half: admission closes (healthz 503
``draining``), in-flight requests finish up to
``FLAGS_serving_drain_timeout_s``, the waiting queue is cancelled with
SSE error frames, the block ledger is blocksan-verified empty-running,
and the prefix cache exports its hash-chain index + block contents
through the PR 5 atomic-manifest machinery into
``FLAGS_serving_prefix_export_dir`` — which a NEW engine imports at
construction (corrupt exports skipped with a counter, never loaded), so
restart-to-first-token on a hot system prompt is warm-cache (+
warm-compile via the persistent compilation cache).

Cold start (ISSUE 7): the set of programs the engine can EVER dispatch
is small and enumerable — one tick program per {steps_per_tick, 1-step
tail} (greedy and sampled share it: sampling params are device inputs
and ``lax.cond`` compiles both branches), the host-sampling k=1 decode
program (``FLAGS_serving_device_sampling`` is read at dispatch, so both
variants warm), and one prefill program per pad bucket.  The pad buckets come from ONE ladder
(``FLAGS_serving_pad_buckets`` or the power-of-two default, clamped to
the block table) shared by admission padding, worst-case block
accounting, and :meth:`ServingEngine.warmup`, which walks exactly that
grid — AOT ``.lower().compile()`` where it works, an inert dummy-input
execution otherwise — so with ``FLAGS_serving_warmup=1`` the compile
tracker records ZERO events once ``run()`` admits traffic.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
from collections import deque
from contextlib import ExitStack, contextmanager
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from ..framework.tensor import Tensor
from ..testing import chaos as _chaos
from ..testing import jaxsan as _jaxsan
from ..observability import compile_tracker as _compile
from ..observability import export as _export
from ..observability import xray as _xray
from ..observability import flight_recorder as _flight
from ..observability import metrics as _metrics
from ..observability import quantiles as _quantiles
from ..observability import span as _span
from . import quant as _squant
from .prefix_cache import PrefixCache

__all__ = ["Request", "ServingEngine", "TickTimeout", "NonFiniteLogits"]

_M_ADMISSIONS = _metrics.counter(
    "serving.admissions", "requests admitted into a decode slot")
_M_REJECTIONS = _metrics.counter(
    "serving.rejections",
    "requests rejected or stalled, by reason: over_context (prompt + "
    "budget exceed max_context), capacity (worst-case blocks exceed the "
    "whole pool — can never fit), pool_exhausted (admission deferred "
    "because the pool is currently drained; counted once per request), "
    "error (admission failed mid-flight)")
_M_TICKS = _metrics.counter(
    "serving.ticks", "scheduler ticks that ran a compiled decode step")
_M_TOKENS = _metrics.counter(
    "serving.tokens_out", "tokens emitted to requests")
_M_TICK_S = _metrics.histogram(
    "serving.tick_seconds", "wall time of one decode tick (k compiled "
    "steps + host scheduling)")
_M_POOL = _metrics.gauge(
    "serving.pool_occupancy", "fraction of physical KV blocks in use")
_M_SLOTS = _metrics.gauge(
    "serving.slot_occupancy", "fraction of batch slots holding a request")
_M_TPS = _metrics.gauge(
    "serving.tokens_per_sec", "decode tokens/sec over the last tick")
_M_SAMPLED = _metrics.counter(
    "serving.sampled_tokens", "tokens drawn by the sampler (device or "
    "host path) rather than argmax")
_M_OVERLAP = _metrics.counter(
    "serving.overlap_dispatches", "ticks dispatched before the previous "
    "tick was harvested (double-buffered fast path)")
_M_BOUNDARIES = _metrics.counter(
    "serving.boundaries", "tick boundaries (the schedule ran with "
    "nothing in flight) by why the tick before was not chained: "
    "`waiting` an arrival, `finished` / `budget_spent` an answer's end, "
    "`chunk_pending` a final prefill chunk, `cancelled`, `stopping`, "
    "`block_tick`, ...; `idle` when nothing was in flight")
_M_PREFIX_HITS = _metrics.counter(
    "serving.prefix_hits", "admissions whose prompt prefix was resident "
    "in the shared-block index (prefill skipped for those blocks)")
_M_PREFIX_MISSES = _metrics.counter(
    "serving.prefix_misses", "admissions that found no resident prefix "
    "(full prefill ran); counted only with the prefix cache enabled")
_M_PREFIX_SHARED = _metrics.counter(
    "serving.prefix_blocks_shared", "physical KV blocks reused from the "
    "prefix index instead of recomputed (incl. copy-on-write sources)")
_M_SPEC_PROPOSED = _metrics.counter(
    "serving.spec_proposed_tokens", "draft tokens proposed to the "
    "speculative verify forward (k per live slot per spec tick); the "
    "acceptance rate is spec_accepted_tokens / spec_proposed_tokens")
_M_SPEC_ACCEPTED = _metrics.counter(
    "serving.spec_accepted_tokens", "draft tokens accepted by the "
    "verify forward (greedy argmax match or rejection-sampling accept)")
_M_SPEC_INELIGIBLE = _metrics.counter(
    "serving.spec_ineligible_slots", "active slots dispatched into a "
    "spec tick with a per-slot emit cap BELOW the tick's k (remaining "
    "budget under k): they ride the same program capped instead of "
    "demoting the whole tick to the plain path")
_M_SPEC_K = _metrics.gauge(
    "serving.spec_k_now", "speculative k of the most recent spec "
    "dispatch (steps through FLAGS_serving_spec_k_ladder when "
    "FLAGS_serving_spec_adaptive drives it)")
_M_SPEC_SLOT_ACC = _metrics.gauge(
    "serving.spec_slot_accept_rate", "per-slot lifetime draft "
    "acceptance rate of the slot's CURRENT request (labelled slot=i; "
    "the adaptive-k controller consumes the engine-wide EWMA of the "
    "same signal)")
_M_PREFILL_CHUNKS = _metrics.counter(
    "serving.prefill_chunks", "chunk prefill programs dispatched by the "
    "continuous-batching scheduler (FLAGS_serving_prefill_chunk > 0: an "
    "arriving prompt is absorbed in bounded chunks between decode ticks "
    "instead of one monolithic prefill)")
_M_SLO_SHEDS = _metrics.counter(
    "serving.slo_sheds", "waiting requests rejected by SLO-aware load "
    "shedding (FLAGS_serving_slo_shed: live TTFT/TPOT p99 over target "
    "AND queue depth over the watermark); every shed also counts on "
    "serving.rejections{reason=slo_shed}")
_M_TICK_ERRORS = _metrics.counter(
    "serving.tick_errors", "tick-loop failures absorbed by the crash-"
    "only guard (ISSUE 15): a dispatch/harvest exception or a tick-"
    "watchdog timeout that evicted the implicated slots (outcome="
    "error) or struck an admission-stage request instead of killing "
    "run()/serve_forever")
_M_POISONED = _metrics.counter(
    "serving.poisoned_requests", "requests quarantined after two "
    "admission-stage strikes (program raised, or prefill logits non-"
    "finite under the NaN watchdog): rejected reason=poisoned instead "
    "of re-crashing every scheduler boundary")
_M_DISPATCH_RETRIES = _metrics.counter(
    "serving.dispatch_retries", "transient serving-program dispatch "
    "failures retried in place (FLAGS_serving_dispatch_retries, "
    "labelled site=); only exhausted retries reach the tick guard")
_M_PREFIX_IMPORT = _metrics.counter(
    "serving.prefix_import_blocks", "physical KV blocks restored from "
    "a drain-time prefix-cache export at engine construction "
    "(FLAGS_serving_prefix_export_dir): each was re-pinned through the "
    "ordinary _alloc/_ref path and is index-evictable under pressure")
_M_PREFIX_IMPORT_SKIP = _metrics.counter(
    "serving.prefix_import_skipped_corrupt", "prefix-cache export "
    "versions SKIPPED at import, by reason=corrupt (manifest/sentinel/"
    "sha256 validation failed — truncation or bit rot) | mismatch "
    "(index readable but from an incompatible engine: different "
    "model/pool geometry or quant mode) | unreadable (payload failed "
    "to parse despite a valid manifest); a skipped version is never "
    "loaded — import falls back to the next older one")

# --- request lifecycle tracing (ISSUE 6): every request's
# enqueue -> admit (queue wait) -> prefill -> first token -> per-tick
# decode -> finish timeline feeds streaming quantile sketches, so
# p50/p90/p99 TTFT/TPOT are readable at any moment from stats(), the
# registry snapshot, or the /metrics scrape — O(1) memory, gated with
# everything else on FLAGS_enable_metrics (off = no timestamps taken).
_M_TTFT = _metrics.quantile(
    "serving.ttft_seconds", "time to first token: request enqueue to the "
    "first output token materialized on the host (queue wait + prefill)")
_M_TPOT = _metrics.quantile(
    "serving.tpot_seconds", "inter-token latency (TPOT): per decoded "
    "token, the harvest-to-harvest gap divided by the tokens it yielded")
_M_E2E = _metrics.quantile(
    "serving.e2e_seconds", "end-to-end request latency: enqueue to the "
    "token that finished the request")
_M_QWAIT = _metrics.quantile(
    "serving.queue_wait_seconds", "enqueue to admission start (deferred "
    "requests accumulate real pool-exhausted wait here)")
_M_SLO = _metrics.counter(
    "serving.slo_violations", "latency SLO breaches, by metric=ttft "
    "(per request, against FLAGS_serving_ttft_slo_ms) or metric=tpot "
    "(per token, against FLAGS_serving_tpot_slo_ms); 0-valued flags "
    "disable the checks")
_M_QUEUE_DEPTH = _metrics.gauge(
    "serving.queue_depth", "requests inside the engine (admission queue "
    "+ running slots)")
_M_RUNNING = _metrics.gauge(
    "serving.running", "batch slots currently holding a request")
_M_WAITING = _metrics.gauge(
    "serving.waiting", "requests queued for admission")
_M_OUTCOMES = _metrics.counter(
    "serving.request_outcomes", "terminal request outcomes, by outcome= "
    "finished | cancelled | error | poisoned | drained | slo_shed | "
    "rejected:<reason>; the fleet federation sums these per replica and "
    "the SLO burn-rate monitor reads error|poisoned as budget burn")


_M_MTP_DRAFTED = _metrics.counter(
    "serving.mtp.drafted", "drafts of the model's own multi-token-"
    "prediction module judged by a verify forward (one a running slot a "
    "self-drafted tick), as counted on the device")
_M_MTP_ACCEPTED = _metrics.counter(
    "serving.mtp.accepted", "... of which the verify forward found to be "
    "the model's own choice (each lets the forward emit a second token)")


_M_SSM_RESETS = _metrics.counter(
    "serving.ssm.state_resets", "slots whose per-slot recurrent state (a "
    "Mamba-2 layer's) a prompt's first chunk started from zero")
_M_SSM_CARRIED = _metrics.counter(
    "serving.ssm.carried_chunks", "prompt chunks that began from the "
    "recurrent state the chunk before them left in their slot")
_M_SSM_BYTES = _metrics.gauge(
    "serving.ssm.state_bytes", "device bytes of the per-slot rows (the "
    "recurrent state and convolution window of every layer and slot)")


_M_RING_BYTES = _metrics.gauge(
    "serving.cache.window_ring_bytes", "device bytes of the window layers' "
    "per-slot rings (K and V of the last `window` positions a slot and "
    "layer), the same whatever the context")
_M_RING_WRITES = _metrics.counter(
    "serving.cache.ring_writes", "positions written into the window "
    "layers' rings (a real prompt row, or a position a tick's forward "
    "wrote for a running slot; each lands in every window layer)")
_M_FULL_BLOCKS = _metrics.gauge(
    "serving.cache.full_blocks_in_use", "paged blocks held by sequences "
    "(only the full-attention layers keep rows in them where the model "
    "has window layers)")
_M_LIVE_TOKENS = _metrics.gauge(
    "serving.cache.live_context_tokens", "context positions of the "
    "sequences in the batch slots (a prompt mid-prefill counts whole)")


_M_BLOCK_FORWARDS = _metrics.counter(
    "serving.block.forwards", "forwards of a block-diffusion tick "
    "(denoising + commit), whole-batch forwards a launch")
_M_BLOCK_REVEALED = _metrics.counter(
    "serving.block.tokens_revealed", "tokens revealed by the denoising "
    "forwards of block-diffusion ticks, by per_forward= how many of a "
    "sequence's block one forward revealed (1..block_length)")


class TickTimeout(RuntimeError):
    """The harvest of a compiled tick did not materialize within
    ``FLAGS_serving_tick_timeout_s`` — a hung device program.  Raised
    inside the tick loop and absorbed by the crash-only guard (the
    implicated slots are evicted ``outcome=error``)."""


class NonFiniteLogits(RuntimeError):
    """A request's host-visible logits went NaN/Inf (flight-recorder
    watchdog probe).  At admission this is a poison strike: the request
    retries once from the back of the queue, then is quarantined
    ``reason=poisoned``."""


class Request:
    """One generation request; results accumulate in `output_ids`."""

    _counter = 0

    def __init__(self, prompt_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 seed: Optional[int] = None, priority: int = 0,
                 trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None,
                 keep_first_logits: bool = False):
        Request._counter += 1
        self.rid = Request._counter
        self.prompt_ids = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.do_sample = do_sample
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        # one integer seed drives BOTH samplers: the host RandomState
        # (prefill's first token + the FLAGS_serving_device_sampling=0
        # fallback) and the per-slot device PRNG key (decode tokens are
        # drawn from fold_in(key(seed), token_position), so a rerun with
        # the same seed reproduces the stream regardless of tick sizes)
        self.seed = int(seed) if seed is not None else self.rid
        self._rng = np.random.RandomState(self.seed)
        self.output_ids: List[int] = []
        # with keep_first_logits: the float32 logits row `[V]` of the
        # prompt's last position, as admission brought it to the host and
        # chose the first token from
        self.keep_first_logits = bool(keep_first_logits)
        self.first_logits: Optional[np.ndarray] = None
        # under block-diffusion generation: for each output token the
        # denoising forward (0-based) of its block's tick that revealed it
        self.reveal_steps: List[int] = []
        # under self-drafting: for each verify forward of this request,
        # in order, (the draft it judged, whether the draft was the
        # model's own choice, the tokens the forward emitted: 1 or 2)
        self.draft_log: List[tuple] = []
        self.done = False
        self.slot: Optional[int] = None
        # scheduler knobs (ISSUE 11): higher priority admits first among
        # waiting requests (FIFO within a priority); cancel() asks the
        # engine to drop the request at its next scheduler boundary
        # (waiting -> dropped, mid-prefill -> aborted, running -> slot
        # evicted + blocks released) — a bare bool store, so the serve
        # endpoint's handler threads may call it without a lock
        self.priority = int(priority)
        self.cancelled = False
        self.shed = False             # rejected by SLO load shedding
        # terminal outcome for the SSE frontend (ISSUE 15): "finished",
        # "cancelled", or an engine-ended reason ("error", "poisoned",
        # "slo_shed", "drained", ...) that becomes the stream's terminal
        # `event: error` frame; None while the request is live
        self.outcome: Optional[str] = None
        # admission-stage poison strikes (program raised / logits went
        # non-finite); at _POISON_STRIKES the request is quarantined
        self._strikes = 0
        # chunked-prefill admission state (engine-owned; the table row
        # lives HERE — shadowing self.tables — until the last chunk
        # lands, so in-flight decode ticks see an all-zero row and
        # treat the slot as idle: no store, or one to the pad block)
        self._prefilling = False
        self._prefill_chunks = 0
        self._chunk_row = None        # np [nb_per_seq] shadow table row
        self._chunk_off = 0           # prompt tokens written so far
        self._chunk_t_admit = None
        self._first_draft = None      # a self-drafter's draft, from the
                                      # prompt's last chunk
        # token stream listener (the SSE endpoint): harvest puts each
        # emitted token id, terminal states put None
        self._stream_q = None
        # lifecycle stamps (perf_counter), always on: four reads a
        # request.  The sketches, `trace` records and /requests rows
        # computed from them stay gated on FLAGS_enable_metrics
        self._t_enqueue: Optional[float] = None
        self._t_admit: Optional[float] = None
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._ticks = 0
        self._prefix_blocks = 0   # shared blocks reused at admission
        self._spec_proposed = 0   # draft tokens proposed for this request
        self._spec_accepted = 0   # ...and accepted by the verify forward
        self._drafter = None      # per-request n-gram table (spec_draft=
                                  # ngram; created lazily at first spec
                                  # dispatch)
        # distributed trace context (ISSUE 17): minted by the fleet
        # router (X-Graft-Trace header) or the caller; threaded into
        # every lifecycle / flight record this request produces so the
        # fleet-trace merge can follow it across processes
        self.trace_id: Optional[str] = trace_id
        self.parent_span: Optional[str] = parent_span
        self.trace: Optional[dict] = None   # final record, set at finish

    def _trace_ctx(self) -> dict:
        """``{trace_id, parent_span}`` when traced, else ``{}`` — the
        splat that tags a lifecycle record with this request's trace."""
        if self.trace_id is None:
            return {}
        ctx = {"trace_id": self.trace_id}
        if self.parent_span is not None:
            ctx["parent_span"] = self.parent_span
        return ctx

    def cancel(self) -> None:
        """Ask the engine to drop this request at its next scheduler
        boundary.  Safe from any thread (the serve endpoint calls it on
        client disconnect / request timeout)."""
        self.cancelled = True

    def _stream_push(self, tok: Optional[int]) -> None:
        q = self._stream_q
        if q is not None:
            q.put(tok)

    def _sample(self, logits_row: np.ndarray) -> int:
        if not self.do_sample:
            return int(np.argmax(logits_row))
        from ..models.generation import _process_logits
        # numpy in, numpy out: the row is already on the host, and a
        # jnp round trip here would put ~10 eager device programs (each
        # compiled at first use, AFTER warmup) on the first-token path
        filtered = _process_logits(
            np.asarray(logits_row, np.float32)[None],
            self.temperature, self.top_k, self.top_p)[0]
        p = np.exp(filtered - filtered.max())
        p = p / p.sum()
        return int(self._rng.choice(len(p), p=p))


class _PendingTick:
    """One compiled decode tick in flight.  `toks` ([B, k] int32) is a
    device handle the host has not blocked on — harvest materializes it;
    a second dispatch may slice its last column first (overlap).

    A SPECULATIVE tick (``spec``) additionally carries the per-slot
    emitted counts / accepted-draft counts and the new seq_lens /
    last-token device handles an overlapped next spec tick chains on
    (the host cannot know the accepted length until harvest)."""

    __slots__ = ("active", "k", "toks", "logits", "reqs", "t0",
                 "device_sampling", "overlapped", "step_no", "san",
                 "spec", "counts", "accepts", "new_lens", "new_last",
                 "chunks", "kcap", "sched_s", "chunk_s", "dispatch_s",
                 "state", "block", "new_draft", "judged")

    def __init__(self, active, k, toks, logits, reqs, t0,
                 device_sampling, step_no, san=None):
        self.active = active
        self.k = k
        self.toks = toks
        self.logits = logits
        self.reqs = reqs
        self.t0 = t0
        self.device_sampling = device_sampling
        self.overlapped = False
        self.step_no = step_no
        self.san = san
        self.spec = False
        self.counts = None
        self.accepts = None
        self.new_lens = None
        self.new_last = None
        # a self-drafted tick: the next drafts, and the drafts it judged
        self.new_draft = None
        self.judged = None
        self.state = ()     # the cache's per-layer state after this tick
        # a block-diffusion tick: (given, new, step_of) — per slot, how
        # many of the block's tokens were the prompt's and how many are to
        # be handed over, and the device handle of each position's
        # revealing forward
        self.block = None
        self.chunks = 0     # prefill chunks run at this tick's boundary
        self.kcap = None    # per-slot emit caps of a spec dispatch
        # per-tick phase breakdown: seconds of the serve:schedule,
        # serve:chunk_dispatch and serve:tick_dispatch spans of this
        # tick's boundary; the harvest's two spans end at harvest
        self.sched_s = 0.0
        self.chunk_s = 0.0
        self.dispatch_s = 0.0


@jax.jit
def _last_column(toks):
    """The [B] last-token column of an in-flight tick's [B, k] token
    handle, which the overlapped next tick chains on.  One compiled
    program (warmed per tick size by `ServingEngine.warmup`): plain
    `toks[:, -1]` on a device array runs as five eager programs, each
    compiled at its first use — after warmup, on the request path."""
    return toks[:, -1]


def _next_tokens(logits, do_sample, temperature, top_k, top_p, seeds,
                 tok_pos, j):
    """One decode step's token choice over [B, V] logits: greedy rows
    argmax, sampling rows draw from fold_in(key(seed), position) over
    the per-row filtered logits; an all-greedy mix skips the [B, V]
    sort at run time.  Shared verbatim by the degree-1 and TP tick
    bodies so the choice math is one definition."""
    from ..models.generation import _process_logits_rows
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn():
        filtered = _process_logits_rows(
            logits.astype(jnp.float32), temperature, top_k, top_p)
        keys = jax.vmap(lambda s, p: jax.random.fold_in(
            jax.random.key(s), p + j))(seeds, tok_pos)
        samp = jax.vmap(jax.random.categorical)(
            keys, filtered).astype(jnp.int32)
        return jnp.where(do_sample, samp, greedy)

    return jax.lax.cond(jnp.any(do_sample), drawn, lambda: greedy)


class _RetryCounter:
    """io_retry counter adapter: every transient-dispatch retry counts
    on the engine AND the process registry."""

    __slots__ = ("_engine",)

    def __init__(self, engine):
        self._engine = engine

    def inc(self, **labels):
        self._engine.dispatch_retries += 1
        _M_DISPATCH_RETRIES.inc(**labels)


def _bucket(n: int, minimum: int) -> int:
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b


# What a positional argument or result of a serving program IS.  A program
# says it once, beside its body (`_Decl`); the shard_map specs, the donated
# positions and warm-up's inert arguments are all read off that.
_PARAMS, _POOLS, _DPARAMS, _DPOOLS, _REP = (
    "params", "pools", "draft_params", "draft_pools", "replicated")


class _In(NamedTuple):
    """A replicated scheduler input of a program (the rank-0 broadcast
    under TP), by shape and dtype."""
    shape: tuple
    dtype: Any
    ones: bool = False      # warm-up's dummy: zeros unless this says ones

    def inert(self):
        """Warm-up's argument.  Inert: all-zero tables and seq_lens route
        every write to the reserved scratch block 0 and hold every slot
        inactive, so warm-up is safe even mid-flight."""
        return (jnp.ones if self.ones else jnp.zeros)(self.shape, self.dtype)


@dataclasses.dataclass(frozen=True)
class _Decl:
    """One serving program: its compile-tracker name, its body, what
    each positional argument is (a kind above, or an `_In`) and what
    each result is (a tuple of kinds, or the one kind of a lone result),
    the cache and key its built callable lives under, and what a
    compile of it is blamed on."""
    name: str
    body: Callable
    args: tuple
    outs: Any
    cache: dict
    key: Any
    blame: tuple = ()
    grid_entry: Optional[dict] = None

    @property
    def grid(self) -> dict:
        """The program's line in `stats()["warmup"]["grid"]`."""
        return self.grid_entry or {
            "program": self.name.split(".", 1)[1], **dict(self.blame)}

    @property
    def donated(self) -> tuple:
        """`donate_argnums`: the pools, which every program threads."""
        return tuple(i for i, a in enumerate(self.args)
                     if a in (_POOLS, _DPOOLS))


class ServingEngine:
    """Continuous batching over a model with `forward_with_cache` +
    paged caches (GPT/Llama families).

    engine = ServingEngine(model, max_batch=4, max_context=512)
    engine.add_request(Request([1, 2, 3], max_new_tokens=16))
    finished = engine.run()          # or engine.step() incrementally
    """

    def __init__(self, model, max_batch: int = 4,
                 max_context: Optional[int] = None, block_size: int = 64,
                 num_blocks: Optional[int] = None,
                 steps_per_tick: int = 1,
                 pad_buckets=None, tp_degree: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 draft_model=None, spec_decode: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_draft: Optional[str] = None,
                 spec_adaptive: Optional[bool] = None,
                 spec_k_ladder=None,
                 quant: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_export_dir: Optional[str] = None):
        # steps_per_tick > 1 compiles a k-step lax.scan per tick so one
        # host round trip harvests k tokens per slot (the host round trip
        # otherwise caps serving at ~1/RTT steps); admissions join at
        # tick boundaries — the standard iteration-level scheduling
        # granularity tradeoff.  Sampling runs on device inside the same
        # scan (per-slot params + PRNG seeds are inputs), so sampled
        # requests keep the full k too.
        self.model = model
        cfg = model.cfg
        self.B = max_batch
        self.bs = block_size
        self.max_context = int(max_context or cfg.max_seq_len)
        self.nb_per_seq = math.ceil(self.max_context / block_size)
        if num_blocks is None:
            num_blocks = max_batch * self.nb_per_seq
        self.num_blocks = num_blocks
        # what a layer caches, and through which views the programs reach
        # it, is the model's to say (a (K, V) pair of pools, or a latent
        # pool with an index-key pool beside it): every program, the
        # copy-on-write and the prefix cache's export go over "the pools
        # of a layer", all under the one block table
        self.cache = model.cache_spec()
        asked = {"tp_degree": int(
                     tp_degree if tp_degree is not None
                     else _flags.get_flag("serving_tp_degree")) > 1,
                 "draft_model": draft_model is not None,
                 "spec_decode": bool(
                     spec_decode if spec_decode is not None
                     else _flags.get_flag("serving_spec_decode")),
                 "quant": bool(quant if quant is not None
                               else _flags.get_flag("serving_quant")),
                 # the flag's default (on) yields to a cache that refuses
                 # it; only an explicit prefix_cache=True is an ask
                 "prefix_cache": bool(prefix_cache)}
        for mech, why in self.cache.unsupported.items():
            if asked.get(mech):
                raise ValueError(f"ServingEngine({mech}=...): {why}")
        # a cache with per-slot rows (a recurrent layer's state, one entry
        # a batch slot): every prompt goes through the chunk programs, each
        # told its slot and how many of its rows are real
        self._per_slot = any(r.per_slot for r in self.cache.rows)
        # how the model generates, if other than one token a forward and
        # sequence (`BlockDiffusion`): the tick then denoises and commits
        # one block a running sequence, and every prompt is absorbed
        # through the chunk programs up to its last whole block
        from ..models.kv_cache import BlockDiffusion, SelfDraft
        generation = self.cache.generation
        self.gen = generation if isinstance(generation, BlockDiffusion) \
            else None
        # ... or a model that drafts for itself through its multi-token-
        # prediction module (`SelfDraft`): the tick is then one forward
        # over (last token, draft) that emits one or two tokens a slot and
        # drafts again, every prompt goes through the chunk programs (they
        # also write the module's rows), and requests are served greedy
        self.mtp = generation if isinstance(generation, SelfDraft) else None
        if self.gen is not None and block_size % self.gen.block_length:
            raise ValueError(
                f"block_size {block_size} must be a multiple of the "
                f"model's block_length {self.gen.block_length}")
        self._sd = model.state_dict()
        self._keys = sorted(self._sd)
        dtype = self._sd[self._keys[0]]._value.dtype
        # --- weight-only quantization (ISSUE 10): snapshot the matmul
        # weights per-channel int8 at construction; every program takes
        # the int8 payload as input and dequantizes IN-trace right
        # before binding (`_bind_params`), so device weight residency is
        # int8.  Like TP, quant implies snapshot semantics: later
        # mutations of the live model tensors do not reach the engine.
        qmode = quant if quant is not None \
            else _flags.get_flag("serving_quant")
        self.quant_mode = str(qmode or "")
        if self.quant_mode and self.quant_mode not in _squant.MODES:
            # checked HERE so the TP plan path fails as loudly as the
            # degree-1 snapshot path (a typo'd mode must not silently
            # serve int8 accuracy)
            raise ValueError(
                f"FLAGS_serving_quant supports {_squant.MODES}; "
                f"got {self.quant_mode!r}")
        self._qw = None
        self._quant_stats = None
        # --- tensor-parallel decode (ISSUE 9): shard the programs over a
        # 'tp' mesh axis — weights column-parallel (heads/FFN/vocab), KV
        # pools along the head axis; the host scheduler stays rank-0 and
        # every replicated input (tables, seq_lens, sampling params) is
        # the broadcast admission.  Degree 1 (the default) is bit-for-bit
        # today's single-program path; >1 snapshots the weights into the
        # sharded layout at construction (live _sd re-binds per dispatch
        # stay a degree-1-only feature).
        self.tp = int(tp_degree if tp_degree is not None
                      else _flags.get_flag("serving_tp_degree"))
        if self.tp < 1:
            raise ValueError(f"serving_tp_degree must be >= 1: {self.tp}")
        self._tp_mesh = None
        self._tp_params = None
        self._tp_specs = None
        self._tp_meta = None
        if self.tp > 1:
            from ..distributed import mesh as _mesh_mod
            from . import tp as _tp
            devs = list(jax.devices())
            if len(devs) < self.tp:
                raise ValueError(
                    f"serving_tp_degree={self.tp} needs {self.tp} local "
                    f"devices; jax sees {len(devs)}")
            self._tp_mesh = _mesh_mod.build_mesh(
                {_tp.AXIS: self.tp}, devices=devs[:self.tp])
            plan = _tp.build_plan(model, self.tp)
            if self.quant_mode:
                # quantize BEFORE sharding: per-channel scales keep
                # their reduced axis, so each rank's (int8, scale)
                # shard dequantizes to an exact slice of the full
                # dequantized matrix — quant x TP stays bit-parity
                _squant.quantize_plan(plan, self.quant_mode)
                self._quant_stats = _squant.plan_stats(plan)
            self._tp_params = _tp.shard_plan(plan, self._tp_mesh)
            self._tp_specs = plan.specs
            self._tp_meta = plan.meta
        elif self.quant_mode:
            self._qw = _squant.snapshot(
                self._keys, [self._sd[k]._value for k in self._keys],
                self.quant_mode)
            self._quant_stats = self._qw.stats()
        # physical pools per layer; block 0 is the pad/scratch block
        # (TP: sharded along the head axis so each rank holds its heads'
        # blocks — the KV-memory scale-out)
        def _place(spec):
            if self._tp_mesh is None:
                return None
            from jax.sharding import NamedSharding
            return lambda row, z: jax.device_put(
                z, NamedSharding(self._tp_mesh, spec))
        main_place = None
        if self._tp_mesh is not None:
            from . import tp as _tp
            main_place = _place(_tp.pool_spec())
        self.pools = self.cache.init_pools(num_blocks, block_size, dtype,
                                           main_place, num_slots=max_batch)
        # --- speculative decoding (ISSUE 10): the draft model proposes
        # spec_k tokens per slot inside one compiled program; the target
        # judges all k proposals in one chunk verify forward
        # (inference/speculative.py has the losslessness contract).  The
        # draft keeps its OWN paged pools indexed by the SAME block
        # table — one allocator/refcount/prefix path covers both models.
        spec = (spec_decode if spec_decode is not None
                else _flags.get_flag("serving_spec_decode"))
        self.spec = bool(spec)
        self.spec_k = int(spec_k if spec_k is not None
                          else _flags.get_flag("serving_spec_k"))
        kind = (spec_draft if spec_draft is not None
                else _flags.get_flag("serving_spec_draft"))
        self.spec_kind = str(kind or "model")
        if self.spec_kind not in ("model", "ngram"):
            raise ValueError(
                "FLAGS_serving_spec_draft supports 'model' or 'ngram'; "
                f"got {self.spec_kind!r}")
        adaptive = (spec_adaptive if spec_adaptive is not None
                    else _flags.get_flag("serving_spec_adaptive"))
        self.spec_adaptive = bool(adaptive)
        # model-draft state only exists for spec_draft='model'
        self.spec_model = self.spec and self.spec_kind == "model"
        self.draft = draft_model if self.spec_model else None
        self.dpools = None
        self._dsd = None
        self._dkeys = None
        self._dqw = None
        self._tp_draft_vals = None
        self._spec_fns = {}       # model-draft spec tick, per ladder k
        self._spec_hd_fns = {}    # host-draft (ngram) twin, per ladder k
        self.spec_ticks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_ineligible_slots = 0
        self.spec_k_switches = 0
        self.spec_ladder: tuple = ()
        self.spec_k_now = 0
        self._accept_ewma: Optional[float] = None
        self._spec_ticks_since_adapt = 0
        if self.spec:
            if self.spec_k < 1:
                raise ValueError(
                    f"serving_spec_k must be >= 1: {self.spec_k}")
            if self.spec_adaptive:
                ladder = (spec_k_ladder if spec_k_ladder is not None
                          else _flags.get_flag("serving_spec_k_ladder"))
                self.spec_ladder = self._parse_spec_ladder(ladder)
            else:
                self.spec_ladder = (self.spec_k,)
            # start at the lowest rung: ramping UP on observed
            # acceptance risks nothing, starting high on an unknown
            # workload wastes whole verify chunks
            self.spec_k_now = self.spec_ladder[0]
        if self.spec and not self.spec_model:
            if draft_model is not None:
                raise ValueError(
                    "spec_draft='ngram' is model-free; drop "
                    "draft_model= (or select spec_draft='model')")
        if self.spec_model:
            if draft_model is None:
                raise ValueError(
                    "speculative decoding needs a draft model: "
                    "ServingEngine(model, draft_model=...) — or select "
                    "spec_draft='ngram', or disable "
                    "FLAGS_serving_spec_decode")
            dcfg = draft_model.cfg
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size {dcfg.vocab_size} != target "
                    f"{cfg.vocab_size}")
            if dcfg.max_seq_len < self.max_context:
                raise ValueError(
                    f"draft max_seq_len {dcfg.max_seq_len} < engine "
                    f"max_context {self.max_context}")
            self._dsd = draft_model.state_dict()
            self._dkeys = sorted(self._dsd)
            if self.quant_mode:
                self._dqw = _squant.snapshot(
                    self._dkeys,
                    [self._dsd[k]._value for k in self._dkeys],
                    self.quant_mode)
            ddtype = self._dsd[self._dkeys[0]]._value.dtype
            self.draft_cache = draft_model.cache_spec()
            from jax.sharding import PartitionSpec
            # draft pools replicate: every rank runs the full (small)
            # draft forward; only the verify is sharded
            self.dpools = self.draft_cache.init_pools(
                num_blocks, block_size, ddtype, _place(PartitionSpec()))
            if self._tp_mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(self._tp_mesh, PartitionSpec())
                vals = (self._dqw.values if self._dqw is not None
                        else [self._dsd[k]._value for k in self._dkeys])
                self._tp_draft_vals = jax.tree_util.tree_map(
                    lambda a: jax.device_put(jnp.asarray(a), rep), vals)
        # host-side scheduler state
        self.tables = np.zeros((max_batch, self.nb_per_seq), np.int32)
        self.seq_lens = np.zeros((max_batch,), np.int32)
        self.last_tok = np.zeros((max_batch,), np.int32)
        # a self-drafting model's draft of the token after `last_tok`
        self.draft_tok = np.zeros((max_batch,), np.int32)
        self._mtp_fn = None
        self._probe_fns = {}
        self.mtp_forwards = 0      # verify forwards x running slots
        self.mtp_accepted = 0
        # per-slot sampling params — device INPUTS of the decode tick
        # (free slots carry the identity: greedy, t=1, no filters)
        self.samp_do = np.zeros((max_batch,), bool)
        self.samp_temp = np.ones((max_batch,), np.float32)
        self.samp_topk = np.zeros((max_batch,), np.int32)
        self.samp_topp = np.ones((max_batch,), np.float32)
        self.samp_seed = np.zeros((max_batch,), np.uint32)
        # tokens DISPATCHED per slot (appended + in-flight): the PRNG
        # stream position and the budget clamp both count these, so an
        # overlapped tick in flight is already accounted for
        self.tok_pos = np.zeros((max_batch,), np.int32)
        self.free_blocks = deque(range(1, num_blocks + 1))
        self.free_slots = deque(range(max_batch))
        self.reserved = 0                      # growth blocks promised
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.waiting: deque = deque()
        self.finished: List[Request] = []
        self.steps = 0
        self._cache_state = None   # (steps, state rows) of the last harvest
        self.ticks = 0
        self.tokens_out = 0
        self.steps_per_tick = max(1, int(steps_per_tick))
        # the scheduler inputs the tick programs declare: (tables,
        # seq_lens, last_tok), and the per-slot sampling (do_sample,
        # temperature, top_k, top_p, seed), whose free-slot identity has
        # ones where warm-up's dummies have them
        B, i32 = (max_batch,), jnp.int32
        self._sched_in = (_In((max_batch, self.nb_per_seq), i32),
                          _In(B, i32), _In(B, i32))
        self._samp_in = (_In(B, jnp.bool_), _In(B, jnp.float32, ones=True),
                         _In(B, i32), _In(B, jnp.float32, ones=True),
                         _In(B, jnp.uint32))
        # the prompt's tail (fewer than block_length tokens) that opens
        # each slot's next block; empty from the second block on
        self.block_tail: List[List[int]] = [[] for _ in range(max_batch)]
        self._block_tick_fn = None
        self._decode_fn = None
        self._tick_fns = {}
        self._prefill_fns = {}
        self._prefill_cont_fns = {}
        self._cow_fn = None
        self._last_harvest_t = None
        # --- prefix/KV reuse (ISSUE 9): physical blocks are refcounted
        # (table references + one per index entry) so a prompt prefix
        # resident in the shared-block index is a pointer copy at
        # admission; rc==1 everywhere when the cache is off, making the
        # alloc/release helpers the single accounting path either way
        self.block_rc = np.zeros((num_blocks + 1,), np.int64)
        # blocksan (ISSUE 12): shadow ledger mirroring every
        # _alloc/_ref/_release, reconciled against tables/shadow rows/
        # prefix index at tick boundaries.  None unless
        # FLAGS_enable_jaxsan was on at construction — the disabled
        # path is one `is None` check per accounting call.
        self._blocksan = _jaxsan.block_ledger(num_blocks)
        enable_prefix = (prefix_cache if prefix_cache is not None
                         else _flags.get_flag("serving_prefix_cache"))
        if "prefix_cache" in self.cache.unsupported:
            enable_prefix = False
        self.prefix = PrefixCache(block_size) if enable_prefix else None
        # the pad-bucket ladder: ONE source of truth for "which prompt
        # shapes exist" — admission padding, worst-case accounting, and
        # the warmup grid all read it (snapshot at construction; the
        # flag is process-wide but a running engine's grid must not
        # shift under an already-taken warmup)
        if pad_buckets is None:
            pad_buckets = _flags.get_flag("serving_pad_buckets")
        ladder = self._parse_pad_buckets(pad_buckets)
        cap = self.nb_per_seq * self.bs
        if ladder:
            ladder = tuple(sorted({min(b, cap) for b in ladder}))
        else:
            ladder = self._default_ladder()
        self.pad_ladder = ladder
        self._warmup_info = None
        # --- chunked prefill (ISSUE 11): absorb arriving prompts in
        # chunks of at most `chunk` tokens, each a suffix-prefill
        # (prefill_cont) program at a traced offset, interleaved between
        # decode ticks by the per-tick scheduler.  Snapshot at
        # construction like the pad ladder: the warmup grid (which
        # programs exist) must not shift under a running engine.
        chunk = (prefill_chunk if prefill_chunk is not None
                 else _flags.get_flag("serving_prefill_chunk"))
        self.chunk = int(chunk)
        if self.chunk < 0:
            raise ValueError(
                f"serving_prefill_chunk must be >= 0: {self.chunk}")
        if (self.mtp is not None or self._per_slot) and self.chunk <= 0:
            self.chunk = self.pad_ladder[-1]
        self.ssm_resets = self.ssm_carried = 0
        # ... of which a recurrent layer's state (a ring is not one)
        self._ssm = any(r.per_slot and not r.window for r in self.cache.rows)
        self.ssm_state_bytes = self.cache.held_bytes(
            self.pools, lambda row: row.per_slot and not row.window)
        if self._ssm:
            _M_SSM_BYTES.set(self.ssm_state_bytes)
        # window layers' rings (`PoolRow.window`) beside the full layers'
        # paged pools: their bytes stay what they are whatever the context
        self._ring = any(r.window for r in self.cache.rows)
        self.ring_writes = 0
        self.window_ring_bytes = self.cache.held_bytes(
            self.pools, lambda row: row.window > 0)
        if self._ring:
            _M_RING_BYTES.set(self.window_ring_bytes)
        if self.gen is not None:
            # chunks begin and end on multiples of the block length (the
            # mask is full inside a block); unchunked, a chunk is as long
            # as the longest bucket
            Lb = self.gen.block_length
            self.chunk = max(Lb, (self.chunk or self.pad_ladder[-1])
                             // Lb * Lb)
        # admissions mid-chunked-prefill, oldest first (the scheduler
        # finishes the oldest before starting the next: chunk budget
        # spent round-robin would inflate EVERY waiting TTFT)
        self.prefilling: deque = deque()
        # --- paged Pallas kernel selection (ISSUE 18): which chunk-view
        # class the suffix/chunked-prefill and spec-verify programs
        # attend through.  Snapshotted here like the pad ladder — the
        # flags must never be read under trace (graft-lint R004), and a
        # running engine's compiled grid must not shift under it.
        pallas_prefill = _flags.get_flag("serving_pallas_prefill")
        self._chunk_view_cls = (
            self.cache.chunk_kernel_view if pallas_prefill
            else self.cache.chunk_view)
        # ... and the draft model's, for its share of a chunk
        self._draft_chunk_view_cls = None if not self.spec_model else (
            self.draft_cache.chunk_kernel_view if pallas_prefill
            else self.draft_cache.chunk_view)
        self._verify_view_cls = (
            self.cache.verify_kernel_view
            if _flags.get_flag("serving_pallas_verify")
            else self.cache.chunk_view)
        self.prefill_chunks_total = 0
        self.overlap_chunks_total = 0
        self.slo_sheds = 0
        self._chunks_this_boundary = 0
        self._chunk_s_this_boundary = 0.0
        # readiness (ISSUE 14 satellite): /healthz answers 503 warmup
        # until run()/serve_forever() finished warmup and opened
        # admission — the SSE frontend must not report healthy while
        # the program grid is still compiling
        self._ready = False
        self._t_serve_start: Optional[float] = None
        # --- crash-only lifecycle (ISSUE 15): drain state + tick-error
        # accounting.  `_drain_requested` is a bare bool store, safe
        # from signal handlers and the POST /drain handler threads;
        # the engine loop turns it into an actual drain() at its next
        # boundary.
        self._draining = False
        self._drain_requested = False
        self._drain_info: Optional[dict] = None
        # the event `serve_forever` runs until, while it runs: a set
        # event ends the chain of ticks (`_can_overlap`)
        self._stop_event = None
        # why the next boundary is one: `_boundary_reason`'s word for the
        # tick `_cycle` last harvested alone, "idle" with none in flight
        self._boundary_why = "idle"
        # --- router evidence (ISSUE 16): always-on (independent of the
        # metrics gate) recent admission timestamps + TTFTs.  /healthz
        # ships rate + median so the fleet router's queue-position
        # model can PREDICT a new request's TTFT instead of waiting
        # for an observed SLO breach.  Host-side floats only.
        self._admit_times: deque = deque(maxlen=64)
        self._ttft_recent: deque = deque(maxlen=64)
        self.tick_errors = 0
        self.poisoned_requests = 0
        self.dispatch_retries = 0
        # --- fleet telemetry evidence (ISSUE 17): always-on, host-side
        # floats only — the federation snapshot and the router's SLO
        # burn-rate monitor read these even with the metrics gate off.
        # _ev_tpot is a tick-level sketch (one harvest gap imputed to
        # the k tokens it yielded), NOT per-request timing: the
        # "tracing off = zero per-request work" pin stays intact.
        self._ev_outcomes: Dict[str, int] = {}
        self._ev_tpot = _quantiles.QuantileSketch()
        self._ev_slo_viol = 0
        self._ev_finished = 0
        self._ev_finished_tokens = 0
        # per-engine flight recorder (fleet replicas run several engines
        # in one process; None = the module-global default recorder)
        self._flight_rec = None
        # live chunks_per_tick controller state (ISSUE 17 satellite:
        # FLAGS_serving_chunks_per_tick_auto); None until first consult
        self._chunk_budget_now: Optional[int] = None
        # warm restart: import the newest valid prefix-cache export
        # (hash-chain index + block KV contents) a draining predecessor
        # left under FLAGS_serving_prefix_export_dir — entries re-pin
        # fresh blocks through _alloc_block, corrupt versions are
        # skipped with a counter, and a hot system prompt's first
        # admission is then a suffix-only prefill
        # per-engine override of FLAGS_serving_prefix_export_dir: an
        # in-process replica fleet (inference/fleet/) gives each engine
        # its own export/import root, which a process-global flag
        # cannot express
        self._export_dir = str(
            prefix_export_dir if prefix_export_dir is not None
            else _flags.get_flag("serving_prefix_export_dir"))
        self._prefix_import_info: Optional[dict] = None
        if self.prefix is not None and self._export_dir:
            self._import_prefix_cache(self._export_dir)

    # ------------------------------------------------------------ programs
    def _views(self, pools, tables, seq_lens, cls=None):
        """One view a layer over its pools (`cls`: the decode / prefill-
        from-empty view unless a chunk view is asked for)."""
        cls = cls or self.cache.view
        return [cls.from_parts(*layer, tables, seq_lens, self.bs)
                for layer in pools]

    def _state_rows(self, pools) -> tuple:
        """A copy of the layers' per-layer counts (the rows of the cache
        neither paged nor per slot), one `[num_layers, ...]` array a row: the
        tick program returns it beside its tokens, so the harvest brings
        it to the host with them and nothing reads a donated pool.  `()`
        for a cache without such rows."""
        return tuple(jnp.stack([layer[i] for layer in pools])
                     for i, row in enumerate(self.cache.rows) if row.counts)

    def _bind(self, param_vals):
        for k, v in zip(self._keys, param_vals):
            self._sd[k]._value = v

    def _bind_params(self, param_vals):
        """Bind a program's parameter INPUT into the live model tensors
        (trace time).  A quantized payload dequantizes in-trace first —
        the dequant-in-matmul seam: XLA fuses the per-channel scale
        multiply into the consuming matmuls, and the program's weight
        inputs stay int8 on device."""
        if self._qw is not None:
            param_vals = _squant.dequant_values(param_vals,
                                                self._qw.axes)
        self._bind(param_vals)

    def _bind_draft(self, draft_vals):
        """Same contract for the draft model (spec decode)."""
        if self._dqw is not None:
            draft_vals = _squant.dequant_values(draft_vals,
                                                self._dqw.axes)
        for k, v in zip(self._dkeys, draft_vals):
            self._dsd[k]._value = v

    def _draft_vals(self):
        """The draft-parameter program input: the TP-replicated or
        quantized snapshot when one exists, else the live tensors (the
        degree-1 fp contract: weight updates reach the next dispatch)."""
        if self._tp_draft_vals is not None:
            return self._tp_draft_vals
        if self._dqw is not None:
            return self._dqw.values
        return [self._dsd[k]._value for k in self._dkeys]

    @contextmanager
    def _params_for_call(self):
        """The program-parameter argument plus the save/restore bracket
        the degree-1 path needs (its programs re-bind the model's live
        tensors while tracing).  TP target programs are pure functions
        of the sharded snapshot — but the draft model is bound at trace
        time in EVERY mode, so its tensors always get the bracket."""
        dsaved = ({k: self._dsd[k]._value for k in self._dkeys}
                  if self._dsd is not None else None)
        try:
            if self._tp_params is not None:
                yield self._tp_params
                return
            vals = (self._qw.values if self._qw is not None
                    else [self._sd[k]._value for k in self._keys])
            saved = {k: self._sd[k]._value for k in self._keys}
            try:
                yield vals
            finally:
                for k, v in saved.items():
                    self._sd[k]._value = v
        finally:
            if dsaved is not None:
                for k, v in dsaved.items():
                    self._dsd[k]._value = v

    def _blame(self, *extra):
        base = (("max_batch", self.B), ("block_size", self.bs))
        if self.tp > 1:
            base = base + (("tp", self.tp),)
        return extra + base

    def _forward(self, params):
        """THE FORWARD SEAM: the one place that knows how this engine
        runs its model.  Called once a program with the program's
        parameter input, it returns ``forward(ids, pools, tables, lens,
        pos_offset, view_cls=None) -> (logits [B, s, V], new_pools)``,
        which the body may call any number of times (the tick calls it
        inside its scan; the block tick says ``in_tick=True``, which the
        views carry to a layer that counts its rows by kind of program; a
        prompt chunk of a cache with per-slot rows says ``slots`` and
        ``n_new``, its sequence's batch slot and real rows).

        Degree 1 binds the parameters into the live model (a quantized
        payload dequantizes here, once, outside any scan) and each call
        goes through views over the pools.  With a TP mesh the forward
        is `tp.forward_tp` on this rank's weight and pool shards, the
        logits replicated by its vocab all-gather: token choice sees the
        FULL logits, so the streams are bit-identical to degree 1.  (The
        draft model keeps its own bind + forward: it runs replicated in
        every mode.)"""
        if self._tp_mesh is not None:
            from . import tp as _tp

            def forward(ids, pools, tables, lens, pos_offset,
                        view_cls=None):
                return _tp.forward_tp(
                    self._tp_meta, params, ids, pools, tables, lens,
                    pos_offset, self.bs,
                    view_cls=view_cls or self.cache.view)
            return forward
        from ..framework.dygraph import no_grad
        self._bind_params(params)

        def forward(ids, pools, tables, lens, pos_offset, view_cls=None,
                    in_tick=False, hidden=False, slots=None, n_new=None):
            views = self._views(pools, tables, lens, view_cls)
            if in_tick:
                for view in views:
                    view.in_tick = True
            if slots is not None:
                # per-slot rows: the batch slots the sequences stand in
                # and the real rows of each (`SlotStateView`)
                for view in views:
                    view.slots, view.n_new = slots, n_new
            if not isinstance(pos_offset, int):
                pos_offset = Tensor._wrap(pos_offset)
            # `hidden`: the last hidden states in the logits' place (a
            # self-drafting model's programs apply the head themselves)
            run = self.model.forward_hidden if hidden \
                else self.model.forward_with_cache
            with no_grad():
                out_t, new_views = run(
                    Tensor._wrap(ids), views, pos_offset=pos_offset)
            return out_t._value, [c.pools for c in new_views]
        return forward

    def _draft(self, pools, tables, lens, h, next_ids, view_cls=None,
               in_tick=False, slots=None, n_new=None):
        """The self-drafter's side of the forward seam (the parameters
        are bound by `_forward`): the module over the positions whose
        hidden states are `h`, `lens` the lengths BEFORE them (`slots`,
        `n_new`: as `_forward`'s, for a cache with per-slot rows).
        Returns (the module's last hidden states `[B, s, H]`, of which
        `_head` gives the logits; new pools)."""
        from ..framework.dygraph import no_grad
        views = self._views(pools, tables, lens, view_cls)
        for view in views:
            view.in_tick = in_tick
            if slots is not None:
                view.slots, view.n_new = slots, n_new
        with no_grad():
            z, new_views = self.model.draft_hidden(h, next_ids, views)
        return z._value, [c.pools for c in new_views]

    def _head(self, h):
        """Logits of hidden states that passed their last norm."""
        from ..framework.dygraph import no_grad
        with no_grad():
            return self.model.head(Tensor._wrap(h))._value

    def _program(self, name, fn, donate, *blame):
        """Jit ``fn`` as the serving program ``name``.  The jitted
        callable is named after the compile-tracker name with ``.`` as
        ``_`` (``serving_tick``), so the trace's ``XLA Modules`` line,
        X-ray's ledger and the compile tracker say the same word.
        ``donate`` is dropped on the CPU, whose PJRT does not donate."""
        fn.__name__ = fn.__qualname__ = name.replace(".", "_")
        if jax.default_backend() == "cpu":
            donate = ()
        return _compile.wrap_first_call(
            jax.jit(fn, donate_argnums=donate), name, self._blame(*blame))

    def _build(self, decl: _Decl):
        """A declared program, built and cached: under a TP mesh its
        body becomes a shard_map whose specs are the declaration's kinds
        — the target parameters take the plan's spec tree, the target
        pools P('tp') (the head axis), everything else P(), the rank-0
        broadcast — and the pools are what it donates.  check_vma off:
        replication of the outputs is guaranteed by construction (every
        rank computes the full logits after the vocab all-gather), which
        the rep-checker cannot always prove through the sampling
        primitives."""
        body = decl.body
        if self._tp_mesh is not None:
            from jax.sharding import PartitionSpec as _P
            from . import tp as _tp
            spec = {_PARAMS: self._tp_specs, _POOLS: _tp.pool_spec()}
            of = lambda kind: spec.get(kind, _P())  # noqa: E731
            body = jax.shard_map(
                body, mesh=self._tp_mesh,
                in_specs=tuple(map(of, decl.args)),
                out_specs=(tuple(map(of, decl.outs))
                           if isinstance(decl.outs, tuple)
                           else of(decl.outs)),
                check_vma=False)
        fn = self._program(decl.name, body, decl.donated, *decl.blame)
        fn.decl = decl
        decl.cache[decl.key] = fn
        return fn

    def _decode_program(self):
        """The host-sampling fallback's k=1 step: it returns the logits
        the per-row host sampler needs beside the greedy tokens."""
        if self._decode_fn is not None:
            return self._decode_fn

        def step(params, pools, tables, seq_lens, last_tok):
            logits, pools = self._forward(params)(
                last_tok[:, None], pools, tables, seq_lens,
                seq_lens[:, None])
            logits = logits[:, -1, :]
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
                logits, pools

        return self._build(_Decl(
            "serving.decode", step, (_PARAMS, _POOLS) + self._sched_in,
            (_REP, _REP, _POOLS), vars(self), "_decode_fn",
            (("variant", "host_sampling_k1"),),
            {"program": "decode", "steps_per_tick": 1}))

    def _tick_program(self, k: int):
        """The fast-path k-step tick with ON-DEVICE sampling.

        Per-slot `do_sample`/`temperature`/`top_k`/`top_p`/`seed` ride
        in as arrays, so one compiled program serves every batch mix
        (the reference samples inside its decode megakernel for the
        same reason).  Each step's token for a sampling row is drawn
        from ``fold_in(key(seed), token_position)`` — the stream is a
        pure function of (seed, position), independent of tick
        boundaries, overlap, or slot placement."""
        fn = self._tick_fns.get(k)
        if fn is not None:
            return fn

        def tick(params, pools, tables, seq_lens, last_tok,
                 do_sample, temperature, top_k, top_p, seeds, tok_pos):
            forward = self._forward(params)

            def body(carry, j):
                pools, lens, last = carry
                logits, new_pools = forward(
                    last[:, None], pools, tables, lens, lens[:, None])
                nxt = _next_tokens(logits[:, -1, :], do_sample,
                                   temperature, top_k, top_p, seeds,
                                   tok_pos, j)
                active = lens > 0
                nxt = jnp.where(active, nxt, 0)
                lens = jnp.where(active, lens + 1, 0)
                return (new_pools, lens, nxt), nxt

            (pools, _, _), toks = jax.lax.scan(
                body, (pools, seq_lens, last_tok), jnp.arange(k))
            return jnp.transpose(toks), pools, \
                self._state_rows(pools)              # [B, k]

        return self._build(_Decl(
            "serving.tick", tick,
            (_PARAMS, _POOLS) + self._sched_in + self._samp_in
            + (_In((self.B,), jnp.int32),),
            (_REP, _POOLS, _REP), self._tick_fns, k,
            (("steps_per_tick", k),)))

    def _block_tick_program(self):
        """The tick of a block-diffusion model (`self.gen`): for every
        running slot, one launch runs the `denoising_steps` denoising
        forwards of its next block and the forward that commits it.

        ``block_toks`` `[B, L]` holds each slot's block as it starts: the
        prompt's tail, then `[MASK]`.  A denoising forward runs the `L`
        positions at ``seq_lens`` against the committed K and V and the
        block itself (its rows are written behind ``seq_lens``, where the
        next forward overwrites them and nothing else looks), takes
        `x0 = argmax`, `log c = max - logsumexp` of the float32 logits at
        each masked position and reveals the `L / denoising_steps` masked
        positions of highest `c` (ties: the lowest position) as their
        `x0`.  The commit forward writes the finished block's K and V.
        Returns (the block's tokens `[B, L]`, for each position the
        denoising forward that revealed it or -1 for a given token
        `[B, L]`, the pools, the state rows).  The host advances
        ``seq_lens`` by `L`."""
        if self._block_tick_fn is not None:
            return self._block_tick_fn
        gen = self.gen
        L, n_steps = gen.block_length, gen.denoising_steps
        per = L // n_steps

        def block_tick(params, pools, tables, seq_lens, block_toks):
            forward = self._forward(params)
            view = self._chunk_view_cls

            def run(pools, toks):
                return forward(toks, pools, tables, seq_lens,
                               seq_lens[:, None], view, in_tick=True)

            def denoise(carry, j):
                pools, toks, step_of = carry
                with jax.named_scope("bd_denoise"):
                    logits, pools = run(pools, toks)
                with jax.named_scope("bd_reveal"):
                    logits = logits.astype(jnp.float32)
                    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    logc = jnp.max(logits, axis=-1) \
                        - jax.nn.logsumexp(logits, axis=-1)
                    masked = step_of == n_steps
                    _, top = jax.lax.top_k(
                        jnp.where(masked, logc, -jnp.inf), per)
                    which = jnp.zeros_like(masked).at[
                        jnp.arange(masked.shape[0])[:, None], top].set(True)
                    which = which & masked
                    toks = jnp.where(which, x0, toks)
                    step_of = jnp.where(which, j, step_of)
                return (pools, toks, step_of), None

            step_of = jnp.where(block_toks == gen.mask_token_id,
                                jnp.int32(n_steps), jnp.int32(-1))
            (pools, toks, step_of), _ = jax.lax.scan(
                denoise, (pools, block_toks, step_of),
                jnp.arange(n_steps, dtype=jnp.int32))
            with jax.named_scope("bd_commit"):
                _, pools = run(pools, toks)
            return toks, step_of, pools, self._state_rows(pools)

        i32 = jnp.int32
        return self._build(_Decl(
            "serving.block_tick", block_tick,
            (_PARAMS, _POOLS, _In((self.B, self.nb_per_seq), i32),
             _In((self.B,), i32), _In((self.B, L), i32)),
            (_REP, _REP, _POOLS, _REP), vars(self), "_block_tick_fn",
            (("block_length", L), ("denoising_steps", n_steps)),
            {"program": "block_tick", "block_length": L,
             "denoising_steps": n_steps}))

    def _mtp_tick_program(self):
        """The tick of a self-drafting model (`self.mtp`): each running
        slot's stream ends in ``last_tok`` (not yet cached) with the
        module's ``draft`` of the token after it.  ONE forward of the
        model over ``[last_tok, draft]`` at positions ``n, n + 1`` (scope
        ``mtp_verify``) gives both positions' logits and hidden states;
        the accept tail of every spec tick (`speculative._finish`, greedy)
        emits ``t_{n+1}`` and, where the draft was it, ``t_{n+2}``, within
        the slot's cap ``kcap``; then the module runs over both positions
        with the emitted tokens (scope ``mtp_draft``), writing its rows,
        and its logits at the last emitted position are the next draft.
        The rejected position's rows (the model's at ``n + 1``, the
        module's in slot ``n + 2``) lie behind the new length and are
        overwritten by the next forward.  Returns (toks `[B, 2]`, counts,
        accepts, new_lens, new_last, new_draft, the draft judged, pools,
        state rows): lens, last and draft are what a chained tick takes
        from the device."""
        if self._mtp_fn is not None:
            return self._mtp_fn
        from . import speculative as _spec
        mtp_row = [r.name for r in self.cache.rows].index("mtp")

        def mtp_tick(params, pools, tables, seq_lens, last_tok, draft,
                     kcap):
            forward = self._forward(params)
            B = self.B
            with jax.named_scope("mtp_verify"):
                h, pools = forward(
                    jnp.stack([last_tok, draft], 1), pools, tables,
                    seq_lens, seq_lens[:, None], None, in_tick=True,
                    hidden=True)
                logits = self._head(h)
            # position 1 has no draft to judge: it is never "accepted"
            dtoks = jnp.stack([draft, jnp.full_like(draft, -1)], 1)
            greedy = (jnp.zeros((B,), bool), jnp.ones((B,), jnp.float32),
                      jnp.zeros((B,), jnp.int32),
                      jnp.ones((B,), jnp.float32),
                      jnp.zeros((B,), jnp.uint32))
            toks, counts, accepts, new_lens, new_last = _spec._finish(
                self, logits, dtoks,
                jnp.zeros(logits.shape[:2] + (1,), jnp.float32), *greedy,
                seq_lens, kcap)
            z, pools = self._draft(pools, tables, seq_lens, h, toks,
                                   in_tick=True)
            with jax.named_scope("mtp_draft"):     # its head is its price
                drafts = jnp.argmax(self._head(z), axis=-1).astype(jnp.int32)
            new_draft = jnp.take_along_axis(
                drafts, jnp.maximum(counts - 1, 0)[:, None], axis=1)[:, 0]
            new_draft = jnp.where(seq_lens > 0, new_draft, 0)
            # the drafter's device-side counts, on the module's layer
            tally = jnp.stack([jnp.sum(seq_lens > 0), jnp.sum(accepts)]
                              ).astype(jnp.int32)
            last = list(pools[-1])
            last[mtp_row] = last[mtp_row] + tally
            pools = list(pools[:-1]) + [tuple(last)]
            return (toks, counts, accepts, new_lens, new_last, new_draft,
                    draft, pools, self._state_rows(pools))

        i32 = jnp.int32
        return self._build(_Decl(
            "serving.mtp_tick", mtp_tick,
            (_PARAMS, _POOLS) + self._sched_in
            + (_In((self.B,), i32), _In((self.B,), i32)),
            (_REP,) * 7 + (_POOLS, _REP), vars(self), "_mtp_fn",
            (("draft", "mtp"), ("depth", self.mtp.depth)),
            {"program": "mtp_tick", "draft": "mtp",
             "depth": self.mtp.depth}))

    def _prompt_program(self, name, cache, L_pad: int, at_offset: bool):
        """Both prompt programs, one a pad bucket: the prompt (or the
        chunk of one) right-padded to ``L_pad`` is written through the
        slot's table row and the last REAL token's logits come back.
        ``serving.prefill`` writes a whole prompt from position 0;
        ``serving.prefill_cont`` takes one more input, the traced scalar
        ``start``, and writes at positions start..start+true_len-1
        through the chunk view (`_prefill_cont_program`).  A spec-model
        engine threads (draft_params, draft_pools) through both, so the
        draft's prompt KV lands in its pools under the same table row."""
        fn = cache.get(L_pad)
        if fn is not None:
            return fn
        view_cls = self._chunk_view_cls if at_offset else None

        def prefill(params, pools, table_row, prompt, true_len, *start):
            forward = self._forward(params)
            if at_offset:
                (off,) = start
                lens = jnp.reshape(off, (1,))
            else:
                lens, off = jnp.zeros((1,), jnp.int32), 0
            logits, pools = forward(prompt, pools, table_row, lens, off,
                                    view_cls)
            if self.gen is not None:
                # a block-diffusion prompt is prefilled for its K and V:
                # its first tokens come from the block tick, not from here
                return jnp.zeros((), jnp.float32), pools
            row = jax.lax.dynamic_index_in_dim(
                logits[0], true_len - 1, axis=0, keepdims=False)
            return row, pools

        def prefill_mtp(params, pools, table_row, prompt, true_len, off,
                        next_ids, *slot):
            """A chunk of a self-drafting model's prompt: the model's
            rows, then the module's from the chunk's own hidden states
            and ``next_ids``, the prompt shifted by one; -1 there stands
            for the token this chunk's last logits choose (the prompt's
            last chunk).  With per-slot rows ``slot`` is the sequence's
            batch slot, as ``prefill_slot``'s.  Returns (the last real
            token's logits, the module's draft of the token after the
            chosen one, pools)."""
            forward = self._forward(params)
            lens = jnp.reshape(off, (1,))
            at = dict(slots=jnp.reshape(slot[0], (1,)),
                      n_new=jnp.reshape(true_len, (1,))) if slot else {}
            h, pools = forward(prompt, pools, table_row, lens, off, view_cls,
                               hidden=True, **at)
            def last_row(x):     # the head over the last real token only
                return self._head(jax.lax.dynamic_slice_in_dim(
                    x, true_len - 1, 1, axis=1))[0, 0]

            row = last_row(h)
            first = jnp.argmax(row).astype(jnp.int32)
            z, pools = self._draft(pools, table_row, lens, h,
                                   jnp.where(next_ids < 0, first, next_ids),
                                   view_cls, **at)
            return row, jnp.argmax(last_row(z)).astype(jnp.int32), pools

        def prefill_slot(params, pools, table_row, prompt, true_len, off,
                         slot):
            """A chunk of the prompt of a model with per-slot rows (a
            recurrent layer's state): its sequence stands in batch slot
            ``slot``, and of its ``L_pad`` rows the first ``true_len`` are
            real (a padded row does not advance the state).  Returns the
            last real token's logits, the head run over that row alone."""
            forward = self._forward(params)
            h, pools = forward(prompt, pools, table_row,
                               jnp.reshape(off, (1,)), off, view_cls,
                               hidden=True,
                               slots=jnp.reshape(slot, (1,)),
                               n_new=jnp.reshape(true_len, (1,)))
            return self._head(jax.lax.dynamic_slice_in_dim(
                h, true_len - 1, 1, axis=1))[0, 0], pools

        def prefill_spec(params, draft_vals, pools, dpools, table_row,
                         prompt, true_len, *start):
            row, pools = prefill(params, pools, table_row, prompt,
                                 true_len, *start)
            self._bind_draft(draft_vals)
            dnew = self._draft_prompt_write(dpools, table_row, prompt,
                                            *start)
            return row, pools, dnew

        i32 = jnp.int32
        ins = (_In((1, self.nb_per_seq), i32), _In((1, L_pad), i32),
               _In((), i32, ones=True))
        if at_offset:
            ins += (_In((), i32),)
        if self.spec_model:
            body, state, outs = prefill_spec, \
                (_PARAMS, _DPARAMS, _POOLS, _DPOOLS), (_REP, _POOLS, _DPOOLS)
        elif self.mtp is not None and at_offset:
            body, state, outs = prefill_mtp, (_PARAMS, _POOLS), \
                (_REP, _REP, _POOLS)
            ins += (_In((1, L_pad), i32),)
            if self._per_slot:
                ins += (_In((), i32),)
        elif self._per_slot and at_offset:
            body, state, outs = prefill_slot, (_PARAMS, _POOLS), \
                (_REP, _POOLS)
            ins += (_In((), i32),)
        else:
            body, state, outs = prefill, (_PARAMS, _POOLS), (_REP, _POOLS)
        return self._build(_Decl(name, body, state + ins, outs, cache,
                                 L_pad, (("L_pad", L_pad),)))

    def _prefill_program(self, L_pad: int):
        return self._prompt_program("serving.prefill", self._prefill_fns,
                                    L_pad, False)

    def _prefill_cont_program(self, L_pad: int):
        """Suffix prefill for a prefix-cache hit or a chunk of a chunked
        prefill: the first ``start`` tokens' KV is already resident
        through the slot's table (shared blocks, or the chunks before);
        this program writes ONLY the suffix chunk (padded to the same
        ladder bucket the full prefill uses — the warmup grid stays
        enumerable).  ``start`` is a traced scalar, so one program per
        bucket serves every split point."""
        return self._prompt_program(
            "serving.prefill_cont", self._prefill_cont_fns, L_pad, True)

    def _draft_prompt_write(self, dpools, table_row, prompt, start=None):
        """Traced helper: run the draft forward over a (padded) prompt
        chunk purely for its KV WRITES — the logits are discarded (the
        request's first token comes from the target prefill).  With
        ``start`` the chunk is a suffix at that offset (prefix-cache
        hit; the shared blocks already hold the prefix's draft KV from
        the admission that registered them)."""
        from ..framework.dygraph import no_grad
        if start is None:
            lens, cls, off = jnp.zeros((1,), jnp.int32), \
                self.draft_cache.view, 0
        else:
            lens, cls, off = jnp.reshape(start, (1,)), \
                self._draft_chunk_view_cls, Tensor._wrap(start)
        dviews = self._views(dpools, table_row, lens, cls)
        with no_grad():
            _, dnew = self.draft.forward_with_cache(
                Tensor._wrap(prompt), dviews, pos_offset=off)
        return [c.pools for c in dnew]

    def _cow_program(self):
        """Copy-on-write block copy: duplicate physical block ``src``
        into ``dst`` across every layer's pools, on device (one program;
        src/dst are traced scalars).  Admission uses it when a shared
        block must receive the recomputed last prompt token.  With spec
        decode the draft pools share the block ids, so the same program
        copies the draft layers too.  (Warm-up copies block 0 onto
        itself.)"""
        if self._cow_fn is not None:
            return self._cow_fn

        from ..ops.pallas_paged import paged_copy_block

        def cow(pools, src, dst, rows=self.cache.rows):
            return [tuple(paged_copy_block(p, src, dst, row.block_axis)
                          if row.paged else p
                          for row, p in zip(rows, layer))
                    for layer in pools]

        def cow_spec(pools, dpools, src, dst):
            return cow(pools, src, dst), \
                cow(dpools, src, dst, self.draft_cache.rows)

        blocks = (_In((), jnp.int32),) * 2
        if self.spec_model:
            body, state, outs = cow_spec, (_POOLS, _DPOOLS), (_POOLS, _DPOOLS)
        else:
            body, state, outs = cow, (_POOLS,), _POOLS
        return self._build(_Decl("serving.cow", body, state + blocks, outs,
                                 vars(self), "_cow_fn"))

    def _spec_program(self, k: int):
        """The compiled MODEL-draft speculative tick for ladder rung
        ``k`` (draft k-step scan + target k-token chunk verify + accept
        masks — `inference/speculative.py`).  Signature: (params,
        draft_params, pools, dpools, tables, seq_lens, last_tok,
        do_sample, temperature, top_k, top_p, seeds, kcap) -> (toks
        [B,k], counts, accepts, new_lens, new_last, pools, dpools).
        Cached PER K — the adaptive ladder steps between compiled
        programs, never recompiles one (every rung is in the warmup
        grid).  Under TP the draft runs replicated while the verify is
        the sharded forward; every scheduler input stays the rank-0
        broadcast."""
        fn = self._spec_fns.get(k)
        if fn is not None:
            return fn
        from . import speculative as _spec
        return self._build(_Decl(
            "serving.spec_tick", _spec.build_spec_tick(self, k),
            (_PARAMS, _DPARAMS, _POOLS, _DPOOLS) + self._sched_in
            + self._samp_in + (_In((self.B,), jnp.int32),),
            (_REP,) * 5 + (_POOLS, _DPOOLS), self._spec_fns, k,
            (("spec_k", k), ("draft", "model"))))

    def _spec_hd_program(self, k: int):
        """The compiled HOST-draft (ngram) speculative tick for ladder
        rung ``k``: the k proposed tokens are a device input, so the
        program is the verify chunk + accept tail alone — no draft
        params or pools in the signature.  (params, pools, tables,
        seq_lens, last_tok, dtoks, do_sample, temperature, top_k,
        top_p, seeds, kcap) -> (toks, counts, accepts, new_lens,
        new_last, pools).  Cached per k like the model twin."""
        fn = self._spec_hd_fns.get(k)
        if fn is not None:
            return fn
        from . import speculative as _spec
        return self._build(_Decl(
            "serving.spec_tick", _spec.build_hostdraft_tick(self, k),
            (_PARAMS, _POOLS) + self._sched_in
            + (_In((self.B, k), jnp.int32),) + self._samp_in
            + (_In((self.B,), jnp.int32),),
            (_REP,) * 5 + (_POOLS,), self._spec_hd_fns, k,
            (("spec_k", k), ("draft", "ngram"))))

    # -------------------------------------------------------------- warmup
    def _warm_call(self, fn, args, aot, install):
        """Consume one program's compile during warmup.

        AOT path: ``.lower().compile()`` the inner jit function, run the
        executable once on the inert dummy args (validates the call
        convention and threads the donated pools through), and install a
        shim that calls the compiled executable directly — later traffic
        never re-enters jit tracing at all.  Anything raising falls back
        to a plain dummy-input call of the wrapped program, which marks
        its `wrap_first_call` tracker entry compiled the ordinary way.
        Returns (program output, used_aot)."""
        inner = getattr(fn, "__wrapped__", None)
        mark = getattr(fn, "_mark_compiled", None)
        entry = getattr(fn, "_xray_entry", None)
        if aot and inner is not None and mark is not None \
                and hasattr(inner, "lower"):
            try:
                t0 = time.perf_counter()
                # the claims capture collects trace-time claim_kernel
                # calls from the Pallas wrappers: interpret-mode kernels
                # leave no custom-call marker in the lowered text, so
                # this is the only evidence channel the coverage audit
                # has for them
                with _xray.capture_kernel_claims() as claims:
                    lowered = inner.lower(*args)
                compiled = lowered.compile()
                # the validation run counts as a dispatch too, so every
                # warmed program is named in the ledger before traffic
                out = _xray.dispatch(entry, compiled, args, {}) \
                    if entry is not None else compiled(*args)
                mark(time.perf_counter() - t0)
                # static cost + kernel audit: cost_analysis() FLOPs/
                # bytes, the custom-call scan of the lowered text, and
                # the trace-time kernel claims (best-effort; never
                # raises)
                _xray.attach_lowered(entry, lowered, claims)

                def shim(*a, _c=compiled, _e=entry):
                    if _e is not None:
                        return _xray.dispatch(_e, _c, a, {})
                    return _c(*a)
                shim.__wrapped__ = inner
                shim._xray_entry = entry
                shim.decl = fn.decl
                install(shim)
                return out, True
            except Exception:  # noqa: BLE001 - AOT is an optimization;
                pass           # the jit path below always works
        return fn(*args), False

    def _grid(self):
        """Every program this engine can ever dispatch, in warm-up
        order, each as the accessor call that builds it: one tick per
        tick size in {steps_per_tick, 1} (greedy and sampled decode
        share each — per-slot sampling params are device inputs and both
        `lax.cond` branches compile), the host-sampling k=1 decode
        program, one spec tick per LADDER rung (adaptive k steps between
        warmed programs, never into a compile), and the prompt programs
        per pad-ladder bucket: the whole-prompt prefill unless the
        engine is CHUNKED (``prefill_chunk > 0``: every admission then
        runs the suffix-prefill programs, so the grid swaps one family
        for the other), the suffix prefill where a prefix hit or a chunk
        can ask for it, and the CoW block copy beside the prefix cache.
        BOTH sampling variants are here regardless of the current
        ``FLAGS_serving_device_sampling``: the flag is read live at
        every dispatch, so a mid-run flip must not route traffic to an
        un-warmed program."""
        if self.gen is not None:
            # one tick whatever the mix, and every prompt through the
            # chunk programs (no copy-on-write: a prefix hit ends on a
            # whole block, behind which the prompt is prefilled afresh)
            return [self._block_tick_program] + [
                partial(self._prefill_cont_program, L)
                for L in self.pad_ladder]
        if self.mtp is not None:
            # one tick whatever the mix, every prompt through the chunk
            # programs, and the copy-on-write a prefix hit always takes
            grid = [self._mtp_tick_program] + [
                partial(self._prefill_cont_program, L)
                for L in self.pad_ladder]
            return grid + ([self._cow_program]
                           if self.prefix is not None else [])
        grid = [partial(self._tick_program, k)
                for k in sorted({self.steps_per_tick, 1}, reverse=True)]
        grid.append(self._decode_program)
        if self.spec:
            spec_program = (self._spec_program if self.spec_model
                            else self._spec_hd_program)
            grid += [partial(spec_program, sk) for sk in self.spec_ladder]
        if self.chunk <= 0:
            grid += [partial(self._prefill_program, L)
                     for L in self.pad_ladder]
        if self.prefix is not None or self.chunk > 0:
            grid += [partial(self._prefill_cont_program, L)
                     for L in self.pad_ladder]
        if self.prefix is not None:
            grid.append(self._cow_program)
        return grid

    def _inert_args(self, decl: _Decl, param_vals, draft_vals=None):
        """The arguments warm-up calls a program with: the engine's own
        parameters and pools in the places the declaration names, an
        inert dummy for every scheduler input."""
        state = {_PARAMS: param_vals, _POOLS: self.pools,
                 _DPARAMS: draft_vals, _DPOOLS: self.dpools}
        return tuple(a.inert() if isinstance(a, _In) else state[a]
                     for a in decl.args)

    def _keep_pools(self, decl: _Decl, out) -> None:
        """The pools a program was given are donated to it: keep the
        ones it returns in their place."""
        outs, out = (decl.outs, out) if isinstance(decl.outs, tuple) \
            else ((decl.outs,), (out,))
        for kind, val in zip(outs, out):
            if kind == _POOLS:
                self.pools = val
            elif kind == _DPOOLS:
                self.dpools = val

    def warmup(self, aot: bool = True) -> dict:
        """Precompile the COMPLETE program grid this engine can ever
        dispatch (`_grid`), before traffic arrives, calling each program
        once with its inert arguments and threading the donated pools
        through.

        Idempotent; returns (and stashes for ``stats()``) ``{warmup_s,
        programs, aot_programs, grid}``.  After warmup, traffic over the
        ladder triggers ZERO compile-tracker events — the acceptance
        criterion ``FLAGS_serving_warmup=1`` buys."""
        if self._warmup_info is not None:
            return self._warmup_info
        t0 = time.perf_counter()
        grid = []
        n_aot = 0
        with self._params_for_call() as param_vals:
            # read once, here: a program's trace leaves its tracers bound
            # in the draft model until this bracket restores it
            dvals = self._draft_vals() if self.spec_model else None
            for program in self._grid():
                fn = program()
                decl = fn.decl
                out, was_aot = self._warm_call(
                    fn, self._inert_args(decl, param_vals, dvals), aot,
                    partial(decl.cache.__setitem__, decl.key))
                self._keep_pools(decl, out)
                if decl.name == "serving.tick":
                    _last_column(out[0])    # the overlap chain's slice
                n_aot += was_aot
                grid.append(decl.grid)
        self._warmup_info = {
            "warmup_s": round(time.perf_counter() - t0, 4),
            "programs": len(grid), "aot_programs": n_aot, "grid": grid}
        return self._warmup_info

    # ----------------------------------------------------------- scheduler
    @staticmethod
    def _parse_pad_buckets(spec) -> tuple:
        """FLAGS_serving_pad_buckets / the `pad_buckets` kwarg: a
        comma-separated string or int sequence; () = use the default
        power-of-two ladder."""
        if spec is None:
            return ()
        if isinstance(spec, str):
            vals = [int(s) for s in
                    (c.strip() for c in spec.split(",")) if s]
        else:
            vals = [int(v) for v in spec]
        if any(v <= 0 for v in vals):
            raise ValueError(
                f"serving_pad_buckets entries must be positive: {vals}")
        return tuple(vals)

    @staticmethod
    def _parse_spec_ladder(spec) -> tuple:
        """FLAGS_serving_spec_k_ladder / the ``spec_k_ladder`` kwarg:
        comma-separated string or int sequence; sorted, deduplicated,
        every rung >= 2 (a 1-rung emits exactly one token per verify —
        that is the PLAIN path's job)."""
        if isinstance(spec, str):
            vals = [int(s) for s in
                    (c.strip() for c in spec.split(",")) if s]
        else:
            vals = [int(v) for v in spec]
        if not vals or any(v < 2 for v in vals):
            raise ValueError(
                "serving_spec_k_ladder needs at least one rung, all "
                f">= 2: {vals}")
        return tuple(sorted(set(vals)))

    def _default_ladder(self) -> tuple:
        """Power-of-two buckets from block_size up, clamped to the block
        table — exactly the shapes the legacy `_pad_bucket` formula
        (min(pow2, capacity)) could produce, materialized so the warmup
        grid can enumerate them."""
        cap = self.nb_per_seq * self.bs
        out, b = [], max(self.bs, 1)
        while b < cap:
            out.append(b)
            b *= 2
        out.append(cap)
        return tuple(out)

    def _pad_bucket(self, L: int) -> int:
        """Prompt pad length: smallest ladder bucket that fits (bounds
        the number of compiled prefill programs), CLAMPED to the
        block-table capacity.  Without the clamp a non-power-of-two
        max_context (e.g. 96 with block_size 16, prompt 70 -> bucket
        128) makes need_now exceed nb_per_seq and admission crashes
        mid-flight leaking blocks (ADVICE r5 #1/#4).  A prompt beyond a
        CUSTOM ladder's top rung falls back to the power-of-two bucket
        (still clamped): the request is served, at the price of one
        compile the tracker blames on the new L_pad."""
        for b in self.pad_ladder:
            if L <= b:
                return b
        return min(_bucket(L, self.bs), self.nb_per_seq * self.bs)

    def add_request(self, req: Request):
        L = len(req.prompt_ids)
        traced = _metrics.enabled()
        if self._draining or self._drain_requested:
            # admission is CLOSED while draining: new traffic belongs
            # on another replica (healthz already answers 503 draining)
            _M_REJECTIONS.inc(reason="draining")
            self._ev_note("rejected:draining")
            if traced:
                self._reject_trace(req, "draining")
            raise ValueError(
                "engine is draining: admission closed (retry against "
                "another replica)")
        if self.gen is not None:
            why = None
            if req.do_sample:
                why = ("sampling", "block-diffusion generation is served "
                       "greedy (tokens are revealed by confidence); "
                       "do_sample is not available")
            elif self.gen.mask_token_id in req.prompt_ids:
                why = ("mask_token", "the prompt holds the model's [MASK] "
                       f"token {self.gen.mask_token_id}")
            if why is not None:
                _M_REJECTIONS.inc(reason=why[0])
                self._ev_note(f"rejected:{why[0]}")
                if traced:
                    self._reject_trace(req, why[0])
                raise ValueError(why[1])
        if self.mtp is not None and req.do_sample:
            # the self-drafted tick verifies greedily: a sampled request
            # is refused here, with the reason, rather than served greedy
            _M_REJECTIONS.inc(reason="sampling")
            self._ev_note("rejected:sampling")
            if traced:
                self._reject_trace(req, "sampling")
            raise ValueError(
                "self-drafting through the multi-token-prediction module "
                "is served greedy; do_sample is not available (build the "
                "model with mtp_draft=False to sample)")
        if L + req.max_new_tokens > self.max_context:
            _M_REJECTIONS.inc(reason="over_context")
            self._ev_note("rejected:over_context")
            if traced:
                self._reject_trace(req, "over_context")
            raise ValueError(
                f"request needs {L + req.max_new_tokens}"
                f" tokens > max_context {self.max_context}")
        # worst-case block need must fit the POOL outright, or admission
        # can never succeed and run() would spin on the waiting queue.
        # Uses the SAME clamped pad formula as _try_admit, so a request
        # accepted here can never out-size the block table at admission.
        worst = self._blocks_for(self._pad_bucket(L)) + max(
            0, self._blocks_for(L + req.max_new_tokens)
            - self._blocks_for(L))
        if worst > self.num_blocks:
            _M_REJECTIONS.inc(reason="capacity")
            self._ev_note("rejected:capacity")
            if traced:
                self._reject_trace(req, "capacity")
            raise ValueError(
                f"request needs {worst} blocks worst-case but the pool "
                f"has {self.num_blocks}; raise num_blocks or lower "
                "max_new_tokens")
        req._t_enqueue = time.perf_counter()
        self.waiting.append(req)
        self._update_pressure()
        return req

    def _reject_trace(self, req: Request, reason: str) -> None:
        """Rejections are lifecycle endpoints too: a scraper reading
        /requests sees WHY traffic bounced, not just that it did."""
        req.outcome = reason
        rec = {"rid": req.rid, "outcome": f"rejected:{reason}",
               "prompt_len": len(req.prompt_ids),
               "max_new_tokens": req.max_new_tokens,
               **req._trace_ctx()}
        req.trace = rec
        self._flightrec().record_event("request", **rec)
        _export.record_request(rec)

    def _flightrec(self) -> "_flight.FlightRecorder":
        """This engine's flight recorder: the injected per-engine one
        (fleet replicas — several engines in one process must not
        interleave their rings) or the module-global default."""
        rec = self._flight_rec
        return rec if rec is not None else _flight.default_recorder()

    def _ev_note(self, outcome: str) -> None:
        """Always-on terminal-outcome tally (fleet federation + SLO
        burn-rate evidence); the metrics twin feeds the scrape."""
        self._ev_outcomes[outcome] = self._ev_outcomes.get(outcome, 0) + 1
        _M_OUTCOMES.inc(outcome=outcome)

    def _blocks_for(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.bs)

    # --------------------------------------------- block refcounting
    # Physical blocks are refcounted so the prefix index and multiple
    # request tables can share them.  With the cache off every block has
    # exactly one reference (its table) and these reduce to the old
    # popleft/append accounting.
    def _alloc_block(self) -> int:
        blk = self.free_blocks.popleft()
        if self._blocksan is not None:
            self._blocksan.alloc(blk)
        self.block_rc[blk] = 1
        return blk

    def _ref_block(self, blk: int) -> None:
        if self._blocksan is not None:
            self._blocksan.ref(blk)
        self.block_rc[blk] += 1

    def _release_block(self, blk: int) -> bool:
        """Drop one reference; frees the block (returns True) only when
        orphaned — a shared block survives its other holders."""
        if self._blocksan is not None:
            self._blocksan.release(blk)
        self.block_rc[blk] -= 1
        if self.block_rc[blk] <= 0:
            self.block_rc[blk] = 0
            self.free_blocks.append(blk)
            return True
        return False

    # ------------------------------------- failure isolation (ISSUE 15)
    _POISON_STRIKES = 2
    _DISPATCH_BACKOFF_S = 0.05

    def _dispatch_call(self, site: str, call):
        """Run one compiled-program dispatch through the chaos site hook
        and the bounded transient-retry policy
        (``FLAGS_serving_dispatch_retries``): a RuntimeError (the
        XlaRuntimeError family) retries in place with the shared
        io_retry exponential backoff before surfacing to the tick
        guard.  With the flag at 0 (default) and no chaos armed this is
        one dict check + the call."""
        def attempt():
            _chaos.inject(site)
            return call()

        retries = int(_flags.get_flag("serving_dispatch_retries"))
        if retries <= 0:
            return attempt()
        from ..distributed.checkpoint.io_retry import call_with_retries
        return call_with_retries(
            attempt, retries=retries, backoff_s=self._DISPATCH_BACKOFF_S,
            site=site, retry_on=(RuntimeError, OSError),
            counter=_RetryCounter(self))

    @contextmanager
    def _staging(self, name: str, shield=None):
        """The leaf span ``name`` around a launch's host-to-device
        transfers, one stretch at the head of the callable
        `_dispatch_call` runs (and retries), so that what is left of the
        parent span is the enqueue: yields ``dev``, which hands one host
        array to the device (a tick's through ``shield``, the private
        copy `_launch_tick` explains); at its end the span carries how
        many ``arrays`` and ``bytes`` went."""
        sent = [0, 0]

        def dev(a):
            if shield is not None:
                a = shield(a)
            sent[0] += 1
            sent[1] += a.nbytes     # the host's count: a jax.Array's
            return jnp.asarray(a)   # own `nbytes` costs 2 us a read

        with _span(name) as sp:
            yield dev
            sp.set(arrays=sent[0], bytes=sent[1])

    def _draw_blocks(self, slot: int, lo: int, hi: int) -> None:
        """Ensure a physical block exists for every position ``lo..hi-1``
        of ``slot`` (all draws covered by the admission's reservation)."""
        for pos in range(lo, hi):
            col = pos // self.bs
            if pos % self.bs == 0 and self.tables[slot, col] == 0:
                self.tables[slot, col] = self._alloc_block()
                self.reserved -= 1
                self.slot_req[slot]._growth_left -= 1

    def _screen_row(self, row, slot: int, req: Request) -> np.ndarray:
        """Host-materialize a prefill logits row and screen it.

        Chaos may corrupt the armed (slot, rid)'s row in place (the
        NaN-forward injection); with the flight-recorder NaN watchdog
        enabled the row is then probed and a non-finite value raises
        :class:`NonFiniteLogits` — BEFORE prefix registration, so a NaN
        prompt can never poison the shared index, and before any token
        is emitted, so the strike/requeue path replays nothing.  With
        the watchdog off (default) the row is materialized exactly as
        `_finish_admission` always did and never reduced."""
        row_np = np.asarray(row)
        if _chaos.nan_payload("serving.prefill", slot=slot, rid=req.rid):
            row_np = np.full_like(row_np, np.nan)
        if _flight.enabled() and not _flight.check_finite(
                float(np.sum(row_np)), site="serving.prefill.logits"):
            raise NonFiniteLogits(
                f"prefill logits non-finite for rid={req.rid}")
        return row_np

    @staticmethod
    def _screens_decode_logits() -> bool:
        return bool(_flight.enabled() or _chaos.active_faults())

    def _screen_decode_logits(self, pend, logits_np):
        """Screen the active rows of the host-sampling decode tick's
        logits as `_readback` brought them to the host (chaos NaN
        injection + watchdog probe).  Returns ``(logits ndarray or None,
        {slot: error})``.  Gated the same way as `_screen_row`: with the
        watchdog off and no chaos armed, nothing is materialized beyond
        what the sampler itself pulls."""
        if not self._screens_decode_logits():
            return logits_np, {}
        logits_np = np.array(logits_np)
        bad: dict = {}
        for slot in pend.active:
            req = pend.reqs[slot]
            if req is None or req.done:
                continue
            if _chaos.nan_payload("serving.decode", slot=slot,
                                  rid=req.rid):
                logits_np[slot] = np.nan
            if _flight.enabled() and not _flight.check_finite(
                    float(np.sum(logits_np[slot])),
                    site="serving.decode.logits"):
                bad[slot] = "non-finite decode logits"
        return logits_np, bad

    def _materialize(self, handle):
        """Block on a tick's device outputs, under the tick watchdog:
        with ``FLAGS_serving_tick_timeout_s`` > 0 the wait runs on a
        helper thread and a harvest that does not materialize in time
        raises :class:`TickTimeout` (the guard then fails the tick)
        instead of wedging the loop on a hung device program."""
        timeout = float(_flags.get_flag("serving_tick_timeout_s"))
        if timeout <= 0:
            _chaos.maybe_delay("serving.harvest")
            return np.asarray(handle)
        box: dict = {}

        def work():
            try:
                _chaos.maybe_delay("serving.harvest")
                box["out"] = np.asarray(handle)
            except BaseException as e:  # noqa: BLE001 - forwarded below
                box["exc"] = e

        t = threading.Thread(target=work, name="serving-harvest",
                             daemon=True)
        t.start()
        t.join(timeout)
        if t.is_alive():
            raise TickTimeout(
                f"tick harvest did not materialize within "
                f"FLAGS_serving_tick_timeout_s={timeout}s — device "
                "program hung or wedged")
        if "exc" in box:
            raise box["exc"]
        return box["out"]

    def _error_evict(self, slot: int, error: str) -> None:
        """Terminal error for one RUNNING slot: trace outcome=error,
        evict (blocks released through the single accounting path),
        close the SSE stream with an error frame."""
        req = self.slot_req[slot]
        if req is None:
            return
        self._flightrec().record_event(
            "slot_error", slot=slot, rid=req.rid, error=error[:200])
        if req._prefilling:
            self._abort_prefill(req, outcome="error")
            return
        self._terminal_trace(req, "error")
        self._evict(slot)
        req._stream_push(None)

    def _strike(self, req: Request, error: str) -> None:
        """One admission-stage poison strike: the request's own program
        raised (prefill dispatch) or its prefill logits went
        non-finite.  First strike re-queues it at the BACK of the
        waiting queue (one more chance — transient-looking failures
        already consumed the in-place retries); at ``_POISON_STRIKES``
        it is quarantined: rejected ``reason=poisoned`` so it stops
        re-crashing every scheduler boundary."""
        req._strikes += 1
        if req._strikes >= self._POISON_STRIKES or req.cancelled \
                or self._draining:
            self.poisoned_requests += 1
            _M_POISONED.inc()
            _M_REJECTIONS.inc(reason="poisoned")
            req.outcome = "poisoned"
            if _metrics.enabled():
                self._reject_trace(req, "poisoned")
            self._flightrec().record_event(
                "poison_quarantine", rid=req.rid, strikes=req._strikes,
                error=error[:200])
            self.finished.append(req)
            req._stream_push(None)
        else:
            self.waiting.append(req)
        self._update_pressure()

    def _abandon(self, pend) -> None:
        """Consume an in-flight tick that will never be harvested (the
        tick-failure path): block BRIEFLY so its device writes finish
        before the implicated blocks are released for reallocation;
        errors and a still-running program past the grace period are
        swallowed — the slots are being evicted anyway."""
        try:
            h = pend.toks
            t = threading.Thread(
                target=lambda: jax.block_until_ready(h), daemon=True)
            t.start()
            t.join(1.0)
        except Exception:  # noqa: BLE001 - best-effort drain
            pass

    def _absorb_failure(self, exc: BaseException, pends) -> bool:
        """The crash-only tick guard's decision point.  Returns True
        when the failure was absorbed (the loop continues), False when
        it must propagate (sanitizer findings stay loud — a swallowed
        JaxsanError would defeat the sanitizer).

        A failure tagged with ``_serving_req`` (admission-stage: the
        request's own prefill/chunk program raised, or its logits went
        non-finite) strikes THAT request — the rest of the batch never
        notices.  Anything else is a tick-level failure: the in-flight
        ticks are abandoned and exactly the slots they covered are
        evicted ``outcome=error`` (attribution is program-granular —
        a whole-batch tick program names no slot)."""
        if isinstance(exc, _jaxsan.JaxsanError):
            return False
        self.tick_errors += 1
        _M_TICK_ERRORS.inc()
        err = f"{type(exc).__name__}: {exc}"[:200]
        req = getattr(exc, "_serving_req", None)
        self._flightrec().record_event(
            "tick_error", error=err,
            scope="request" if req is not None else "tick",
            rid=getattr(req, "rid", None))
        if req is not None:
            self._strike(req, err)
            return True
        slots = set()
        for p in pends:
            if p is None:
                continue
            self._abandon(p)
            slots.update(p.active)
        if not slots:
            slots = set(s for s in range(self.B)
                        if self.slot_req[s] is not None)
        for slot in sorted(slots):
            r = self.slot_req[slot]
            if r is None:
                continue
            if r.done:
                self._evict(slot)
            else:
                self._error_evict(slot, err)
        self._last_harvest_t = None
        self._update_occupancy()
        return True

    def _try_admit(self) -> bool:
        if not self.waiting or not self.free_slots:
            return False
        # the boundary's admission decision, a leaf of serve:schedule:
        # queue order, prefix lookup, the capacity check
        with _span("serve:admit"):
            self._promote_waiting()
            req = self.waiting[0]
            L = len(req.prompt_ids)
            chunked = self.chunk > 0
            # the prompt tokens a hit may stand for: all but the last, whose
            # logits are the first token — or, under block diffusion, the
            # whole blocks (no logits are needed of a prompt)
            reusable = L - 1 if self.gen is None else self._prefill_len(req)
            # --- prefix lookup: the longest resident full-block prefix is a
            # pointer copy; reuse is capped at L-1 so at least one suffix
            # token runs forward (its logits are the request's first token).
            # The cap makes copy-on-write exactly the fully-cached aligned
            # case: the last prompt token must be recomputed INTO a block the
            # index still shares.
            chain: List[int] = []
            cached_len = 0
            match = None
            if self.prefix is not None:
                # a deferred request retries every loop iteration: cache its
                # lookup across retries (the hash chain is O(prompt)) —
                # valid only within the index epoch, since an eviction could
                # free-and-reallocate a matched block under us
                match = getattr(req, "_prefix_match", None)
                if match is None \
                        or getattr(req, "_prefix_epoch", -1) \
                        != self.prefix.epoch:
                    match = self.prefix.lookup(req.prompt_ids)
                    req._prefix_match = match
                    req._prefix_epoch = self.prefix.epoch
                chain = match.blocks
                cached_len = min(len(chain) * self.bs, reusable)
                if self.mtp is not None:
                    # the module's row in a block's first slot is made of the
                    # hidden state of the token before the block: the last
                    # shared token is recomputed (into a copy of its block,
                    # the copy-on-write below) so that the request's first
                    # private slot can be written
                    cached_len = min(cached_len, len(chain) * self.bs - 1)
                if cached_len <= 0:
                    chain, cached_len = [], 0
            split_col = cached_len // self.bs
            cow = bool(chain) and (cached_len % self.bs != 0)
            mtp_slot = 1 if self.mtp is not None else 0
            if chain or chunked:
                # exact blocks for the real prompt span: suffix/chunk writes
                # go through PagedChunkView, whose padded positions route to
                # the pad block — no bucket over-allocation to release
                # (a self-drafter's module keeps its row of position L - 1
                # in slot L)
                need_now = self._blocks_for(L + mtp_slot) - split_col
            else:
                L_pad = self._pad_bucket(L)
                need_now = self._blocks_for(L_pad)  # <= nb_per_seq by clamp
            # full reservation: prompt blocks now + growth to the worst case
            total_need = self._blocks_for(L + req.max_new_tokens)
            growth = max(0, total_need - self._blocks_for(L + mtp_slot))
            # pin the reused blocks BEFORE any index eviction can run: a
            # chain entry freed and reallocated under us would alias garbage
            for b in chain[:split_col]:
                self._ref_block(b)
            cow_src = chain[split_col] if cow else None
            if cow_src is not None:
                self._ref_block(cow_src)

            def unpin():
                for b in chain[:split_col]:
                    self._release_block(b)
                if cow_src is not None:
                    self._release_block(cow_src)

            short = need_now + growth - (len(self.free_blocks) - self.reserved)
            if short > 0 and self.prefix is not None:
                # pool pressure: orphaned index blocks are reclaimable —
                # evict leaf entries (LRU) until the admission fits or
                # nothing evictable remains.  Entries whose block is still
                # table-referenced are skipped (freeing them gains nothing
                # and would only cold-start a hot prefix)
                self.prefix.evict(short, self._release_block,
                                  lambda b: int(self.block_rc[b]) == 1)
                short = need_now + growth \
                    - (len(self.free_blocks) - self.reserved)
            if short > 0:
                unpin()
                # admission deferred on a drained pool: counted ONCE per
                # request so rejected/stalled traffic is diagnosable from
                # the metrics snapshot alone (the request stays queued and
                # admits when evictions return blocks)
                if not getattr(req, "_deferral_counted", False):
                    req._deferral_counted = True
                    _M_REJECTIONS.inc(reason="pool_exhausted")
                return False
            self.waiting.popleft()
            # admission starts NOW: everything before this point was queue
            # wait (incl. pool-exhausted deferrals — the tail /metrics must
            # surface under overload)
            t_admit = time.perf_counter()
        # the launch of the prompt's first program: the whole prefill
        # (legacy mode), or the blocks and the copy-on-write of a shared
        # one (chunked mode; the chunks follow as serve:chunk_dispatch)
        sp = _span("serve:prefill_dispatch", rid=req.trace_id or req.rid,
                   prompt_tokens=L).begin()
        slot = self.free_slots.popleft()
        blocks = [self._alloc_block() for _ in range(need_now)]
        table_row = np.zeros((self.nb_per_seq,), np.int32)
        for col, b in enumerate(chain[:split_col]):
            table_row[col] = b
        for i, b in enumerate(blocks):
            table_row[split_col + i] = b
        req._growth_left = growth
        self.reserved += growth
        if chunked:
            # chunked admission: the prompt is absorbed between decode
            # ticks by the per-tick scheduler, not here
            try:
                return self._begin_chunked(req, slot, table_row, chain,
                                           split_col, cow_src, cached_len,
                                           t_admit)
            finally:
                sp.end()
        self.tables[slot, :] = table_row

        # legacy mode: the whole prefill and, from its host sync on, the
        # admission's tail (serve:first_token: prefix registration, the
        # first token's sampling and stream push, with nothing enqueued
        # behind the prompt's program) lie inside the span
        with ExitStack() as spans:
            spans.callback(sp.end)
            try:
                with self._params_for_call() as param_vals:
                    # spec-decode engines thread (draft_params, draft_pools)
                    # through admission so the draft model's prompt KV lands
                    # in its pools via the same table row / block ids
                    dpref = ((self._draft_vals(), self.pools, self.dpools)
                             if self.spec_model else (self.pools,))
                    if chain:
                        if cow_src is not None:
                            # the shared block holds the cached positions of
                            # the last prompt block; copy it so the suffix
                            # write lands in a private block
                            cow_args = ((self.pools, self.dpools)
                                        if self.spec_model else (self.pools,))
                            out = self._cow_program()(
                                *cow_args, jnp.int32(cow_src),
                                jnp.int32(self.tables[slot, split_col]))
                            if self.spec_model:
                                self.pools, self.dpools = out
                            else:
                                self.pools = out
                            dpref = ((dpref[0], self.pools, self.dpools)
                                     if self.spec_model else (self.pools,))
                        Ls = L - cached_len
                        L_pad_s = self._pad_bucket(Ls)
                        suffix = np.zeros((1, L_pad_s), np.int32)
                        suffix[0, :Ls] = req.prompt_ids[cached_len:]
                        # private table-row copy: same R002 aliasing contract
                        # as the full-prefill call below
                        out = self._dispatch_call(
                            "serving.prefill.dispatch",
                            lambda: self._prefill_cont_program(L_pad_s)(
                                param_vals, *dpref,
                                jnp.asarray(
                                    self.tables[slot:slot + 1].copy()),
                                jnp.asarray(suffix), jnp.int32(Ls),
                                jnp.int32(cached_len)))
                    else:
                        prompt = np.zeros((1, L_pad), np.int32)
                        prompt[0, :L] = req.prompt_ids
                        # the table row must be a PRIVATE copy (graft-lint
                        # R002): jnp.asarray of the numpy view aliases
                        # zero-copy, and both the error path and the
                        # pad-block release below mutate self.tables before
                        # np.asarray(row) syncs — an in-flight prefill would
                        # read the mutated block ids
                        out = self._dispatch_call(
                            "serving.prefill.dispatch",
                            lambda: self._prefill_program(L_pad)(
                                param_vals, *dpref,
                                jnp.asarray(
                                    self.tables[slot:slot + 1].copy()),
                                jnp.asarray(prompt), jnp.int32(L)))
                    if self.spec_model:
                        row, self.pools, self.dpools = out
                    else:
                        row, self.pools = out
                    # host-sync + NaN screen BEFORE the prefix registers
                    # anything (a poisoned prompt must not enter the index)
                    spans.enter_context(_span("serve:first_token"))
                    row = self._screen_row(row, slot, req)
            except BaseException as e:
                # admission failed mid-flight: undo every host-side draw so
                # nothing leaks (references dropped — shared blocks survive
                # their other holders — slot freed, growth reservation
                # returned); the request is dropped from the queue and the
                # error propagates, tagged with the request so the tick
                # guard can strike/quarantine it instead of dying
                for col in range(self.nb_per_seq):
                    if self.tables[slot, col]:
                        self._release_block(int(self.tables[slot, col]))
                        self.tables[slot, col] = 0
                if cow_src is not None:
                    self._release_block(cow_src)
                self.free_slots.appendleft(slot)
                self.reserved -= growth
                req._growth_left = 0
                _M_REJECTIONS.inc(reason="error")
                try:
                    e._serving_req = req
                except Exception:   # exotic exception types without a dict
                    pass
                raise
            if cow_src is not None:
                self._release_block(cow_src)   # copy dispatched; pin over
            if not chain:
                # release pad-bucket blocks beyond the prompt's real span
                # (their stale contents are masked by seq_lens and
                # overwritten by any future owner before becoming visible)
                keep = self._blocks_for(L)
                for col in range(keep, need_now):
                    self._release_block(int(self.tables[slot, col]))
                    self.tables[slot, col] = 0
            if self.prefix is not None:
                # register this prompt's full blocks as shareable: reused
                # entries are touched, new full-block columns become entries
                # (one index reference each).  Registered blocks are never
                # written again: decode starts at position L, which lives in
                # an unregistered (partial or fresh) column.
                fullb = L // self.bs
                self.prefix.register(
                    req.prompt_ids,
                    [int(self.tables[slot, c]) for c in range(fullb)],
                    self._ref_block, match=match)
                shared = split_col + (1 if cow_src is not None else 0)
                req._prefix_blocks = shared
                if chain:
                    self.prefix.hits += 1
                    _M_PREFIX_HITS.inc()
                    self.prefix.blocks_shared += shared
                    if shared:
                        _M_PREFIX_SHARED.inc(shared)
                else:
                    self.prefix.misses += 1
                    _M_PREFIX_MISSES.inc()
                # checksum the just-registered blocks (ground truth now;
                # immutable from here) — no-op unless blocksan is armed
                _jaxsan.blocksan_snapshot(self)
            self._finish_admission(req, slot, row, t_admit)
        return True

    def _finish_admission(self, req, slot, row, t_admit) -> None:
        """Shared admission tail (monolithic and chunked): host-sync the
        prefill logits into the first token, stamp queue-wait/TTFT, and
        activate the slot for decode ticks."""
        L = len(req.prompt_ids)
        _M_ADMISSIONS.inc()
        req._t_admit = t_admit
        if _metrics.enabled():
            _M_QWAIT.observe(t_admit - req._t_enqueue)
        req.slot = slot
        self.slot_req[slot] = req
        if self.gen is not None:
            # block diffusion: the slot stands at the prompt's last whole
            # block; its first tokens (and its TTFT) come with its first
            # block tick
            p0 = self._prefill_len(req)
            self.seq_lens[slot] = p0
            self.block_tail[slot] = list(req.prompt_ids[p0:])
            self._update_occupancy()
            return
        row = np.asarray(row)
        first = req._sample(row)
        # np.asarray(row) above was the host sync: the first token
        # really exists now, so this is TTFT, not enqueue time
        if req.keep_first_logits:
            req.first_logits = row
        self._note_first_token(req, time.perf_counter())
        req.output_ids.append(first)
        req._stream_push(first)
        self.seq_lens[slot] = L
        self.last_tok[slot] = first
        if self.mtp is not None:
            self.draft_tok[slot] = int(req._first_draft)
            req._first_draft = None
        self.samp_do[slot] = req.do_sample
        self.samp_temp[slot] = req.temperature
        self.samp_topk[slot] = max(0, int(req.top_k))
        self.samp_topp[slot] = req.top_p
        self.samp_seed[slot] = np.uint32(req.seed & 0xFFFFFFFF)
        self.tok_pos[slot] = len(req.output_ids)
        self.tokens_out += 1
        _M_TOKENS.inc()
        self._update_occupancy()
        self._maybe_finish(req, first)

    def _note_first_token(self, req, t_first: float) -> None:
        """Stamp a request's first token and feed the TTFT evidence."""
        req._t_first = req._t_last = t_first
        ttft = t_first - req._t_enqueue
        slo = _flags.get_flag("serving_ttft_slo_ms")
        late = slo > 0 and ttft * 1e3 > slo
        if _metrics.enabled():
            _M_TTFT.observe(ttft)
            if late:
                _M_SLO.inc(metric="ttft")
        # router evidence (always on, unlike the metrics-gated sketches
        # above): the /healthz TTFT predictor needs admission rate and
        # recent TTFTs even on engines running with metrics disabled,
        # and the fleet burn-rate monitor its tally of SLO violations
        self._admit_times.append(t_first)
        self._ttft_recent.append(ttft)
        if late:
            self._ev_slo_viol += 1

    def _free_capacity(self) -> int:
        """Free blocks INCLUDING those held only by the prefix index —
        the allocator reclaims them on demand (index eviction), so every
        observability surface (stats, the pool gauge, flight records)
        reports the same number: what an admission could actually get."""
        free = len(self.free_blocks)
        if self.prefix is not None:
            free += self.prefix.reclaimable(self.block_rc)
        return free

    def _update_occupancy(self):
        _M_POOL.set(round(1.0 - self._free_capacity()
                          / max(self.num_blocks, 1), 4))
        _M_SLOTS.set(round(1.0 - len(self.free_slots) / max(self.B, 1), 4))
        self._update_pressure()

    def _update_pressure(self):
        # registered scheduler-pressure gauges (ISSUE 6 satellite): the
        # exporter shows queue depth without calling into the engine
        running = self.B - len(self.free_slots)
        _M_RUNNING.set(running)
        _M_WAITING.set(len(self.waiting))
        _M_QUEUE_DEPTH.set(running + len(self.waiting))

    def _maybe_finish(self, req: Request, tok: int):
        if req.done:
            return
        if (req.eos_token_id is not None and tok == req.eos_token_id) or \
                len(req.output_ids) >= req.max_new_tokens:
            req.done = True
            req.outcome = "finished"
            self._ev_note("finished")
            self._ev_finished += 1
            self._ev_finished_tokens += len(req.output_ids)
            req._stream_push(None)      # close the SSE token stream
            if _metrics.enabled():
                self._finish_trace(req)

    def _finish_trace(self, req: Request) -> None:
        """Request reached its terminal token: close the lifecycle trace
        — e2e into the sketch, the per-request record into the flight
        ring (post-mortem) and the /requests export ring (scrape)."""
        t = time.perf_counter()
        e2e = t - req._t_enqueue
        _M_E2E.observe(e2e)
        n_out = len(req.output_ids)
        rec = {"rid": req.rid, "outcome": "finished",
               "prompt_len": len(req.prompt_ids), "tokens_out": n_out,
               "ticks": req._ticks,
               "queue_wait_s": round(req._t_admit - req._t_enqueue, 6),
               "prefill_s": round(req._t_first - req._t_admit, 6),
               "ttft_s": round(req._t_first - req._t_enqueue, 6),
               "tpot_mean_s": round((t - req._t_first)
                                    / max(n_out - 1, 1), 6),
               "e2e_s": round(e2e, 6),
               "prefix_blocks": req._prefix_blocks,
               "prefill_chunks": req._prefill_chunks,
               **req._trace_ctx()}
        if self.spec:
            rec["spec_accept_rate"] = round(
                req._spec_accepted / max(req._spec_proposed, 1), 4)
            rec["spec_draft"] = self.spec_kind
        req.trace = rec
        self._flightrec().record_event("request", **rec)
        _export.record_request(rec)

    def _evict(self, slot: int):
        req = self.slot_req[slot]
        # return the part of the growth reservation this request never
        # drew (early eos); drawn blocks were decremented at allocation
        self.reserved -= getattr(req, "_growth_left", 0)
        req._growth_left = 0
        for col in range(self.nb_per_seq):
            if self.tables[slot, col]:
                # drop the table reference; blocks shared with the
                # prefix index (or another slot) survive the eviction
                self._release_block(int(self.tables[slot, col]))
                self.tables[slot, col] = 0
        self.seq_lens[slot] = 0
        self.last_tok[slot] = 0
        self.draft_tok[slot] = 0
        self.block_tail[slot] = []
        self.samp_do[slot] = False
        self.samp_temp[slot] = 1.0
        self.samp_topk[slot] = 0
        self.samp_topp[slot] = 1.0
        self.samp_seed[slot] = 0
        self.tok_pos[slot] = 0
        self.slot_req[slot] = None
        self.free_slots.append(slot)
        self.finished.append(req)
        self._update_occupancy()

    def _active_slots(self):
        # a slot mid-chunked-prefill is occupied but NOT decodable: its
        # seq_len stays 0 (the tick treats the row as inert) and its
        # table row stays all-zero until the last chunk installs it
        return [s for s in range(self.B)
                if self.slot_req[s] is not None
                and not self.slot_req[s]._prefilling]

    # -------------------------------------- per-tick scheduler (ISSUE 11)
    def _boundary_schedule(self) -> None:
        """The scheduler work of one REAL tick boundary.

        Order of business: propagate cancellations (waiting -> dropped,
        mid-prefill -> aborted, running -> evicted with blocks
        released), shed SLO-doomed arrivals, then admit.  Legacy mode
        (``FLAGS_serving_prefill_chunk`` = 0) keeps the historical
        admit-then-evict order and whole-prompt admissions.  Chunked
        mode budgets the boundary as "up to
        ``FLAGS_serving_prefill_chunks_per_tick`` chunk programs":
        finish the oldest in-flight prefill first, then begin new
        admissions — so every running stream's inter-token gap is
        bounded by (chunk budget x one chunk) + one decode tick no
        matter how long the arriving prompts are."""
        # the boundary's clean-up, a leaf of serve:schedule: what block
        # release and the bookkeeping of ended requests cost
        with _span("serve:reap"):
            for slot in list(range(self.B)):
                req = self.slot_req[slot]
                if req is None or not req.cancelled:
                    continue
                if req._prefilling:
                    self._abort_prefill(req, outcome="cancelled")
                elif not req.done:
                    self._terminal_trace(req, "cancelled")
                    self._evict(slot)
                    req._stream_push(None)
            if self.waiting and any(r.cancelled for r in self.waiting):
                kept = deque()
                for r in self.waiting:
                    if r.cancelled:
                        self._terminal_trace(r, "cancelled")
                        self.finished.append(r)
                        r._stream_push(None)
                    else:
                        kept.append(r)
                self.waiting = kept
                self._update_pressure()
            self._shed_waiting()
            if self.chunk > 0:
                # chunked: evict finished FIRST — their slots and blocks
                # fund this boundary's chunk budget
                self._evict_done()
        if self.chunk <= 0:
            while self._try_admit():
                pass
            with _span("serve:reap"):
                self._evict_done()
            return
        budget = max(1, int(_flags.get_flag(
            "serving_prefill_chunks_per_tick")))
        if _flags.get_flag("serving_chunks_per_tick_auto"):
            budget = self._auto_chunk_budget(budget)
        spent = 0
        while spent < budget:
            if self.prefilling:
                req = self.prefilling[0]
                self._prefill_chunk_step(req)
                if not req._prefilling and self.prefilling \
                        and self.prefilling[0] is req:
                    self.prefilling.popleft()
                spent += 1
                continue
            # beginning an admission is host-only bookkeeping (+ at
            # most one CoW copy) — it costs no chunk budget; its first
            # chunk, dispatched by the next loop pass, does
            if not self._try_admit():
                break

    def _auto_chunk_budget(self, max_budget: int) -> int:
        """Live chunks-per-tick controller (ISSUE 17 satellite,
        FLAGS_serving_chunks_per_tick_auto): walk the budget one step at
        a time inside [1, FLAGS_serving_prefill_chunks_per_tick] from
        the always-on tick-level TPOT sketch against the TPOT SLO.
        Running p90 over target -> spend fewer chunk programs per
        boundary (decode gaps shrink); p90 under half the target ->
        spend more (prompts absorb faster).  No SLO or too little
        evidence: hold.  Only the BUDGET moves — which chunk programs
        exist is fixed at construction, so the warmup grid and program
        signatures never change."""
        cur = self._chunk_budget_now
        if cur is None:
            cur = max_budget
        cur = min(cur, max_budget)          # flag lowered at runtime
        target_ms = float(_flags.get_flag("serving_tpot_slo_ms"))
        if target_ms > 0 and self._ev_tpot.count >= 16:
            p90 = self._ev_tpot.quantile(0.9)
            if p90 is not None:
                if p90 * 1e3 > target_ms:
                    cur = max(1, cur - 1)
                elif p90 * 1e3 < 0.5 * target_ms:
                    cur = min(max_budget, cur + 1)
        self._chunk_budget_now = cur
        return cur

    def _evict_done(self) -> None:
        for slot in list(range(self.B)):
            req = self.slot_req[slot]
            if req is not None and not req._prefilling and req.done:
                self._evict(slot)

    def _promote_waiting(self) -> None:
        """Move the highest-priority waiting request (FIFO within a
        priority) to the queue head.  All-equal priorities keep strict
        FIFO — the head stays put and legacy behavior is unchanged."""
        if len(self.waiting) < 2:
            return
        best = 0
        for i in range(1, len(self.waiting)):
            if self.waiting[i].priority > self.waiting[best].priority:
                best = i
        if best:
            req = self.waiting[best]
            del self.waiting[best]
            self.waiting.appendleft(req)

    def _slo_breached(self) -> bool:
        """Are the LIVE p99 sketches over a configured SLO?  Shed
        decisions consult observed violation, not a prediction; with
        metrics off the sketches are empty and nothing ever sheds."""
        ttft_slo = _flags.get_flag("serving_ttft_slo_ms")
        if ttft_slo > 0 and _M_TTFT.count() \
                and _M_TTFT.quantile(0.99) * 1e3 > ttft_slo:
            return True
        tpot_slo = _flags.get_flag("serving_tpot_slo_ms")
        if tpot_slo > 0 and _M_TPOT.count() \
                and _M_TPOT.quantile(0.99) * 1e3 > tpot_slo:
            return True
        return False

    def _shed_waiting(self) -> None:
        """SLO-aware load shedding (``FLAGS_serving_slo_shed``): while
        the engine is ALREADY violating its latency targets and the
        waiting queue is deeper than the watermark, reject the newest
        lowest-priority waiting requests (reason=slo_shed) instead of
        queueing them into certain violations.  Consulted inputs: the
        live TTFT/TPOT p99 sketches + queue depth — not just pool
        capacity."""
        if not self.waiting or not _flags.get_flag("serving_slo_shed"):
            return
        depth = int(_flags.get_flag("serving_shed_queue_depth"))
        if len(self.waiting) <= depth or not self._slo_breached():
            return
        while len(self.waiting) > depth:
            # victim: lowest priority; newest within a priority (the
            # oldest requests keep their queue-time investment)
            victim = len(self.waiting) - 1
            for i in range(len(self.waiting) - 2, -1, -1):
                if self.waiting[i].priority \
                        < self.waiting[victim].priority:
                    victim = i
            req = self.waiting[victim]
            del self.waiting[victim]
            req.shed = True
            self.slo_sheds += 1
            _M_SLO_SHEDS.inc()
            _M_REJECTIONS.inc(reason="slo_shed")
            if _metrics.enabled():
                self._reject_trace(req, "slo_shed")
            self.finished.append(req)
            req._stream_push(None)
        self._update_pressure()

    def _begin_chunked(self, req, slot, row, chain, split_col, cow_src,
                       cached_len, t_admit) -> bool:
        """Chunked-prefill admission: stash the allocated table row on
        the REQUEST (a shadow row — ``self.tables[slot]`` stays
        all-zero, so decode ticks dispatched mid-prefill see an idle
        slot and store nothing for it, or its row into the pad block,
        instead of corrupting freshly written chunks), dispatch the CoW
        copy if a shared block must receive suffix writes, and queue the
        request for the per-tick chunk budget."""
        if cow_src is not None:
            try:
                cow_args = ((self.pools, self.dpools) if self.spec_model
                            else (self.pools,))
                out = self._cow_program()(
                    *cow_args, jnp.int32(cow_src),
                    jnp.int32(int(row[split_col])))
                if self.spec_model:
                    self.pools, self.dpools = out
                else:
                    self.pools = out
            except BaseException:
                for b in row:
                    if b:
                        self._release_block(int(b))
                self._release_block(cow_src)          # the pin
                self.free_slots.appendleft(slot)
                self.reserved -= req._growth_left
                req._growth_left = 0
                _M_REJECTIONS.inc(reason="error")
                raise
            self._release_block(cow_src)   # copy dispatched; pin over
        req.slot = slot
        req._chunk_row = row
        req._chunk_off = cached_len
        req._chunk_t_admit = t_admit
        req._prefilling = True
        req._prefill_chunks = 0
        self.slot_req[slot] = req
        if self.prefix is not None:
            req._prefix_blocks = split_col + (1 if cow_src is not None
                                              else 0)
            if chain:
                self.prefix.hits += 1
                _M_PREFIX_HITS.inc()
                self.prefix.blocks_shared += req._prefix_blocks
                if req._prefix_blocks:
                    _M_PREFIX_SHARED.inc(req._prefix_blocks)
            else:
                self.prefix.misses += 1
                _M_PREFIX_MISSES.inc()
        if cached_len >= self._prefill_len(req):
            # block diffusion: the prompt's whole blocks were all hits
            # (or it has none), and its tail opens the first block
            self._complete_chunked(req, None)
        else:
            self.prefilling.append(req)
        self._update_occupancy()
        return True

    def _prefill_len(self, req) -> int:
        """The prompt tokens the chunk programs absorb: all of them, or
        under block diffusion its whole blocks (the tail opens the first
        block of the generation)."""
        L = len(req.prompt_ids)
        return L if self.gen is None else L - L % self.gen.block_length

    def _prefill_chunk_step(self, req) -> None:
        """Dispatch ONE bounded prefill chunk for an in-flight chunked
        admission: suffix tokens [off, off+n) padded to their ladder
        bucket through the suffix-prefill program (``start`` = off is a
        traced scalar — zero new programs, bit-identical writes and
        offset causal mask).  The LAST chunk's logits row is the
        request's first token."""
        slot = req.slot
        L = self._prefill_len(req)
        off = req._chunk_off
        n = min(self.chunk, L - off)
        L_pad = self._pad_bucket(n)
        final = off + n >= L

        def launch():
            with self._staging("serve:chunk_stage") as dev:
                suffix = np.zeros((1, L_pad), np.int32)
                suffix[0, :n] = req.prompt_ids[off:off + n]
                # private row copy: same R002 aliasing contract as the
                # monolithic prefill's table-row argument
                args = [dev(req._chunk_row[None, :].copy()), dev(suffix),
                        dev(jnp.int32(n)), dev(jnp.int32(off))]
                if self.mtp is not None:
                    # the prompt shifted by one, for the module's rows;
                    # behind the prompt's last token stands the one this
                    # chunk chooses
                    nxt = np.zeros((1, L_pad), np.int32)
                    follow = req.prompt_ids[off + 1:off + n + 1]
                    nxt[0, :len(follow)] = follow
                    if final:
                        nxt[0, n - 1] = -1
                    args.append(dev(nxt))
                if self._per_slot:
                    args.append(dev(jnp.int32(slot)))
            return self._prefill_cont_program(L_pad)(
                param_vals, *dpref, *args)

        # the chunk's host side (async enqueue): the boundary's
        # chunk-prefill phase.  The LAST chunk host-syncs its logits row
        # inside, and from there to the span's end the admission's tail
        # (the shadow row, prefix registration, the first token's sampling
        # and stream push, nothing enqueued behind the prompt's program)
        # is its child serve:first_token
        with _span("serve:chunk_dispatch", rid=req.trace_id or req.rid,
                   q_tokens=n, kv_tokens=off + n,
                   selected_tokens=self._selected(
                       off + 1 + np.arange(n))) as sp, ExitStack() as tail:
            try:
                with self._params_for_call() as param_vals:
                    dpref = ((self._draft_vals(), self.pools, self.dpools)
                             if self.spec_model else (self.pools,))
                    out = self._dispatch_call(
                        "serving.prefill.dispatch", launch)
                if self.spec_model:
                    row, self.pools, self.dpools = out
                elif self.mtp is not None:
                    row, req._first_draft, self.pools = out
                else:
                    row, self.pools = out
                if final:
                    # last chunk: host-sync + NaN screen before the shadow
                    # row installs and the prefix registers (same contract
                    # as the monolithic path's _screen_row placement)
                    tail.enter_context(_span("serve:first_token"))
                    row = self._screen_row(row, slot, req)
            except BaseException as e:
                self._abort_prefill(req)
                _M_REJECTIONS.inc(reason="error")
                try:
                    e._serving_req = req
                except Exception:
                    pass
                raise
            req._chunk_off = off + n
            req._prefill_chunks += 1
            self._count_ring(n)
            if self._ssm:
                if off:
                    self.ssm_carried += 1
                    _M_SSM_CARRIED.inc()
                else:
                    self.ssm_resets += 1
                    _M_SSM_RESETS.inc()
            self.prefill_chunks_total += 1
            self._chunks_this_boundary += 1
            _M_PREFILL_CHUNKS.inc()
            if _metrics.enabled():
                self._flightrec().record_event(
                    "prefill_chunk", rid=req.rid, slot=slot, start=off,
                    tokens=n, done=final)
            if final:
                self._complete_chunked(req, row)
        self._chunk_s_this_boundary += sp.seconds

    def _complete_chunked(self, req, row) -> None:
        """Last chunk landed: install the shadow table row (the slot
        becomes decodable), register the prompt's full blocks in the
        prefix index — registration HAD to wait, chunk c+1 still writes
        blocks chunk c filled and registered blocks are immutable —
        and run the shared admission tail."""
        slot = req.slot
        L = len(req.prompt_ids)
        self.tables[slot, :] = req._chunk_row
        req._prefilling = False
        req._chunk_row = None
        if self.prefix is not None:
            fullb = L // self.bs
            self.prefix.register(
                req.prompt_ids,
                [int(self.tables[slot, c]) for c in range(fullb)],
                self._ref_block,
                match=getattr(req, "_prefix_match", None))
            _jaxsan.blocksan_snapshot(self)
        self._finish_admission(req, slot, row, req._chunk_t_admit)

    def _abort_prefill(self, req, outcome: Optional[str] = None) -> None:
        """Tear down a mid-chunked-prefill admission: release every
        shadow-row block reference (shared blocks survive their other
        holders), return the slot and the growth reservation.  With
        ``outcome`` (cancellation) the request also gets a terminal
        trace and lands in ``finished``."""
        slot = req.slot
        for b in req._chunk_row:
            if b:
                self._release_block(int(b))
        req._chunk_row = None
        req._prefilling = False
        self.reserved -= req._growth_left
        req._growth_left = 0
        self.slot_req[slot] = None
        self.free_slots.appendleft(slot)
        req.slot = None
        try:
            self.prefilling.remove(req)
        except ValueError:
            pass
        if outcome is not None:
            self._terminal_trace(req, outcome)
            self.finished.append(req)
            req._stream_push(None)
        self._update_occupancy()

    def _terminal_trace(self, req, outcome: str) -> None:
        """Non-finish lifecycle endpoints (cancellations, errors,
        drains) get a trace record too, metrics-gated like everything
        else; the outcome itself is stamped unconditionally — the SSE
        terminal frame needs it regardless of the metrics gate."""
        req.outcome = outcome
        self._ev_note(outcome)
        if not _metrics.enabled():
            return
        rec = {"rid": req.rid, "outcome": outcome,
               "prompt_len": len(req.prompt_ids),
               "max_new_tokens": req.max_new_tokens,
               "tokens_out": len(req.output_ids),
               **req._trace_ctx()}
        req.trace = rec
        self._flightrec().record_event("request", **rec)
        _export.record_request(rec)

    def step(self) -> bool:
        """One SYNCHRONOUS scheduler tick: run the boundary schedule
        (evict finished, spend the admission/chunk budget), run one
        compiled decode tick over the current mix and harvest it.
        Returns True while work remains.  UNGUARDED — exceptions
        propagate to the caller; the serve loops wrap it (or their own
        cycles) in the crash-only guard."""
        return self._harvest_step(self._dispatch_tick(boundary=True))

    def _harvest_step(self, pend) -> bool:
        """The second half of `step()`: harvest the tick it launched."""
        if pend is None:
            return bool(self.waiting or self.prefilling)
        self._harvest_tick(pend)
        return True

    def _guarded_step(self) -> bool:
        """`step()` under the crash-only guard: a dispatch/harvest
        failure is absorbed by `_absorb_failure` (request strike or
        implicated-slot eviction) and the loop stays alive; only
        sanitizer findings (JaxsanError) still propagate."""
        pend = None
        try:
            pend = self._dispatch_tick(boundary=True)
            return self._harvest_step(pend)
        except Exception as e:  # noqa: BLE001 - the guard's whole job
            if not self._absorb_failure(e, (pend,)):
                raise
            return True

    def _dispatch_tick(self, boundary: bool = True, chain=None):
        """Launch one compiled decode tick and return it IN FLIGHT.

        At a tick ``boundary`` the scheduler work runs first (admit
        what fits, evict finished).  ``chain`` is the previous in-flight
        `_PendingTick` (the overlap path): its on-device outputs feed
        straight back in instead of the host arrays.  JAX async
        dispatch means the returned `_PendingTick.toks` is a device
        handle nothing has blocked on; host seq_lens/tok_pos advance
        NOW so a second dispatch sees the in-flight state."""
        sched_s = chunk_s = 0.0
        if boundary:
            self._chunk_s_this_boundary = 0.0
            # why the tick before was not chained (`_cycle` keeps the
            # word `_boundary_reason` gave it), or that none was in flight
            why, self._boundary_why = self._boundary_why, "idle"
            _M_BOUNDARIES.inc(why=why)
            with _span("serve:schedule", waiting=len(self.waiting),
                       running=self.B - len(self.free_slots),
                       why=why) as sp:
                self._boundary_schedule()
            # the boundary's host phases: the chunk dispatches nest
            # inside serve:schedule (their seconds were summed by
            # _prefill_chunk_step); the rest of it (cancel/shed/admit/
            # evict) is the record's schedule phase
            chunk_s = self._chunk_s_this_boundary
            sched_s = max(0.0, sp.seconds - chunk_s)
        active = self._active_slots()
        if not active:
            return None
        t0 = time.perf_counter()
        # host dispatch phase: enqueue cost by design (the compute lands
        # in the harvest wait; a sampled program blocks inside the call)
        lens = self.seq_lens[active]
        with _span("serve:tick_dispatch", active=len(active),
                   kv_tokens=int(lens.sum()),
                   kv_blocks=int((-(-lens // self.bs)).sum()),
                   selected_tokens=self._selected(lens),
                   chained=int(chain is not None)) as sp:
            pend = self._launch_tick(active, t0, chain)
            sp.set(steps=pend.k)
            if self._per_slot:
                # the slots whose per-slot rows each step updates
                sp.set(state_rows=len(active))
            if pend.block is not None:
                sp.set(block_len=self.gen.block_length)
        pend.dispatch_s = sp.seconds
        pend.chunks = self._chunks_this_boundary
        self._chunks_this_boundary = 0
        pend.sched_s, pend.chunk_s = sched_s, chunk_s
        return pend

    def _count_ring(self, positions: int) -> None:
        """Positions written into the window layers' rings."""
        if self._ring:
            self.ring_writes += positions
            _M_RING_WRITES.inc(positions)

    def cache_counts(self) -> dict:
        """What the cache of a model with window layers holds now (host
        data): the rings' bytes (the same whatever the context), the paged
        blocks the sequences hold (only full layers keep rows in them),
        the context positions of the sequences in the batch slots (a
        prompt mid-prefill counts whole: its admission drew the blocks of
        all of it), and the positions written into the rings so far.  The
        gauges of the metrics registry are set from it."""
        live = sum(len(r.prompt_ids) if r._prefilling
                   else int(self.seq_lens[s])
                   for s, r in enumerate(self.slot_req) if r is not None)
        out = {"window_ring_bytes": self.window_ring_bytes,
               "full_blocks_in_use": self.num_blocks - len(self.free_blocks),
               "live_context_tokens": live,
               "ring_writes": self.ring_writes}
        _M_FULL_BLOCKS.set(out["full_blocks_in_use"])
        _M_LIVE_TOKENS.set(live)
        return out

    def cache_state(self) -> dict:
        """Per-layer device state the programs thread with the pools (an
        expert layer's row counts), `[num_layers, ...]` a row by its
        name, as the last harvested tick brought it to the host, and
        `steps`, the decode steps run up to and with that tick.  Host
        data, safe from any thread: the pools themselves are donated to
        the program in flight and never read.  Empty for a cache with no
        such rows, and before the first harvest."""
        if self._cache_state is None:
            return {}
        steps, arrays = self._cache_state
        names = [r.name for r in self.cache.rows if r.counts]
        return dict(zip(names, arrays), steps=steps)

    # ------------------------------------------------- probe (public)
    def take_blocks(self, n: int) -> List[int]:
        """Draw ``n`` free blocks for a `probe` (the ordinary allocator:
        refcounted, ledgered); hand them back with `give_blocks`."""
        return [self._alloc_block() for _ in range(n)]

    def give_blocks(self, blocks) -> None:
        for b in blocks:
            self._release_block(int(b))

    def copy_block(self, src: int, dst: int) -> None:
        """Copy block ``src`` onto ``dst`` in every layer's pools (the
        copy-on-write program an admission runs)."""
        args = (self.pools, self.dpools) if self.spec_model \
            else (self.pools,)
        out = self._cow_program()(*args, jnp.int32(src), jnp.int32(dst))
        if self.spec_model:
            self.pools, self.dpools = out
        else:
            self.pools = out

    def probe(self, ids, tables, seq_lens, *, chunk: bool = False,
              next_ids=None, slot: int = 0) -> dict:
        """Run the engine's own forward over its own pools, outside the
        serve loop, and hand back what the serving programs keep on the
        device: the float32 ``logits`` `[s, V]` of sequence ``slot``.
        ``ids`` `[B, s]` are appended at ``seq_lens`` `[B]` through
        ``tables`` `[B, nb]` by the forward seam, the views and the
        kernels the engine's programs use: the chunk view with ``chunk``
        (one sequence at an offset, a prompt chunk's program), else the
        decode view as a tick holds it (the whole batch; a zero table row
        is an idle slot).  For a self-drafting model ``next_ids`` `[B, s]`
        (the tokens that follow) also runs its module over the same
        positions, writing its rows, and ``draft_logits`` `[s, V]` comes
        back too.  The pools are threaded and donated as the programs do
        it; the caller owns the blocks the tables name (`take_blocks`,
        or the prefix cache's, which must only be read).  A check's tool:
        each distinct shape compiles a program of its own."""
        if chunk and self._per_slot:
            raise ValueError("probe: a chunk of a cache with per-slot rows "
                             "needs its batch slot; read the chunk's logits "
                             "off the timed path (Request.keep_first_logits)")
        ids = jnp.asarray(ids, jnp.int32)
        drafting = self.mtp is not None and next_ids is not None
        key = (ids.shape, bool(chunk), drafting, int(slot))
        fn = self._probe_fns.get(key)
        if fn is None:
            view_cls = self._chunk_view_cls if chunk else None
            f32 = jnp.float32

            def body(params, pools, tables, lens, ids, *nxt):
                forward = self._forward(params)
                args = (ids, pools, tables, lens, lens[:, None], view_cls)
                if self.mtp is None:
                    logits, pools = forward(*args, in_tick=not chunk)
                    return pools, (logits[slot].astype(f32),)
                h, pools = forward(*args, in_tick=not chunk, hidden=True)
                out = (self._head(h[slot]).astype(f32),)
                if nxt:
                    z, pools = self._draft(pools, tables, lens, h, nxt[0],
                                           view_cls, in_tick=not chunk)
                    out += (self._head(z[slot]).astype(f32),)
                return pools, out

            fn = self._probe_fns[key] = jax.jit(
                body, donate_argnums=() if jax.default_backend() == "cpu"
                else (1,))
        nxt = (jnp.asarray(next_ids, jnp.int32),) if drafting else ()
        with self._params_for_call() as param_vals:
            self.pools, out = fn(
                param_vals, self.pools, jnp.asarray(tables, jnp.int32),
                jnp.asarray(seq_lens, jnp.int32), ids, *nxt)
        return dict(zip(("logits", "draft_logits"), out))

    def _selected(self, contexts) -> int:
        """Cached tokens the queries of these contexts attend to, summed:
        all of a context, or the cache's `attend_limit` of it under
        sparse selection (the spans' `selected_tokens`)."""
        limit = self.cache.attend_limit
        c = np.asarray(contexts)
        return int((np.minimum(c, limit) if limit else c).sum())

    def _launch_tick(self, active, t0, chain):
        """Enqueue the tick program over ``active`` (the speculative
        one where eligible) and advance the host's view of the slots."""
        if self.gen is not None:
            return self._launch_block_tick(active, t0)
        if self.mtp is not None:
            return self._dispatch_mtp(active, t0, chain)
        device_sampling = _flags.get_flag("serving_device_sampling")
        # a chained dispatch continues its predecessor's kind (the
        # overlap gate matched them); at a boundary, spec eligibility is
        # re-evaluated against the live budgets
        use_spec = (bool(chain.spec) if chain is not None
                    else self._spec_eligible(active, device_sampling))
        if use_spec:
            return self._dispatch_spec(active, t0, chain)
        k = self._tick_size(active)
        # ensure a physical block exists for every position this tick
        # will write (all draws covered by the admission reservation)
        for slot in active:
            self._draw_blocks(slot, int(self.seq_lens[slot]),
                              int(self.seq_lens[slot]) + k)
        # device inputs get PRIVATE host copies: async dispatch returns
        # before the program consumes them, and jax device_put may alias
        # numpy memory zero-copy — without the copy, this tick's own
        # post-dispatch bookkeeping (and any overlapped next tick's
        # block draws) would race the in-flight program's reads.  The
        # copy is routed through the jaxsan shield (a plain .copy() with
        # FLAGS_enable_jaxsan off): checksummed at dispatch, verified at
        # harvest, so reintroducing the aliasing bug fails loudly
        san = _jaxsan.token("serving.tick")
        # host-sampling fallback: the k=1 program returns the logits the
        # per-row host sampler needs.  Else the one k-step tick program;
        # with sampling off the demotion guarantees no sampled row is
        # active, the all-False mask takes the greedy cond branch
        host_sampling = not device_sampling and k == 1
        last = None if chain is None else _last_column(chain.toks)

        def launch():
            with self._staging("serve:tick_stage",
                               partial(_jaxsan.shield, san)) as dev:
                tok = dev(self.last_tok) if last is None else last
                args = [dev(self.tables), dev(self.seq_lens), tok]
                if not host_sampling:
                    args += [dev(self.samp_do), dev(self.samp_temp),
                             dev(self.samp_topk), dev(self.samp_topp),
                             dev(self.samp_seed), dev(self.tok_pos)]
            program = self._decode_program() if host_sampling \
                else self._tick_program(k)
            return program(param_vals, self.pools, *args)

        logits, state = None, ()
        with self._params_for_call() as param_vals, \
                _flight.guard("serving.tick"):
            out = self._dispatch_call("serving.tick.dispatch", launch)
            if host_sampling:
                greedy, logits, self.pools = out
                toks = greedy[:, None]
            else:
                toks, self.pools, state = out
        self.steps += k
        for slot in active:
            self.seq_lens[slot] += k
            self.tok_pos[slot] += k
        self._count_ring(k * len(active))
        pend = _PendingTick(active=active, k=k, toks=toks, logits=logits,
                            reqs=list(self.slot_req), t0=t0,
                            device_sampling=device_sampling,
                            step_no=self.steps, san=san)
        pend.state = state
        return pend

    def _launch_block_tick(self, active, t0):
        """Enqueue the block tick over ``active``: each slot's next block
        (the prompt's tail, then `[MASK]`) is denoised and committed, and
        the host's view of the slot moves a block on.  Of the block's new
        tokens a request takes what its budget has left; the rest are
        dropped at harvest."""
        gen = self.gen
        Lb = gen.block_length
        toks_in = np.full((self.B, Lb), gen.mask_token_id, np.int32)
        given = np.zeros((self.B,), np.int32)
        new = np.zeros((self.B,), np.int32)
        for slot in active:
            req = self.slot_req[slot]
            tail = self.block_tail[slot]
            toks_in[slot, :len(tail)] = tail
            given[slot] = len(tail)
            new[slot] = min(Lb - len(tail),
                            req.max_new_tokens - int(self.tok_pos[slot]))
            # the block lies inside one pool block (block_size is a
            # multiple of its length): draw it if the block opens one
            pos = int(self.seq_lens[slot])
            col = pos // self.bs
            if self.tables[slot, col] == 0:
                self.tables[slot, col] = self._alloc_block()
                self.reserved -= 1
                req._growth_left -= 1
        san = _jaxsan.token("serving.tick")

        def launch():
            with self._staging("serve:tick_stage",
                               partial(_jaxsan.shield, san)) as dev:
                args = [dev(self.tables), dev(self.seq_lens), dev(toks_in)]
            return self._block_tick_program()(
                param_vals, self.pools, *args)

        with self._params_for_call() as param_vals, \
                _flight.guard("serving.tick"):
            toks, step_of, self.pools, state = self._dispatch_call(
                "serving.tick.dispatch", launch)
        forwards = gen.denoising_steps + 1
        self.steps += forwards
        for slot in active:
            self.seq_lens[slot] += Lb
            self.tok_pos[slot] += int(new[slot])
            self.block_tail[slot] = []
        pend = _PendingTick(active=active, k=forwards, toks=toks,
                            logits=None, reqs=list(self.slot_req), t0=t0,
                            device_sampling=True, step_no=self.steps,
                            san=san)
        pend.state = state
        pend.block = (given, new, step_of)
        return pend

    def _spec_eligible(self, active, device_sampling) -> bool:
        """May this tick run draft/verify?  Needs the subsystem, on-
        device sampling (the host sampler cannot verify), and at least
        ONE active slot able to absorb more than a single token —
        eligibility is PER SLOT now (each slot carries its own emit cap
        into the program), so a short-budget slot merely rides capped
        instead of demoting the whole tick to the plain path.  Only a
        batch where nobody could beat the plain tick falls back."""
        if not self.spec or not device_sampling:
            return False
        need = min(2, self.spec_k_now)
        for slot in active:
            req = self.slot_req[slot]
            if req.max_new_tokens - int(self.tok_pos[slot]) >= need:
                return True
        return False

    # adaptive-k controller constants: step up while the acceptance
    # EWMA clears _ADAPT_UP (proposals are nearly free tokens — reach
    # further), down when it sinks under _ADAPT_DOWN (the verify chunk
    # is mostly wasted width), after at least _ADAPT_MIN_TICKS spec
    # ticks at the current rung (hysteresis against single-tick noise).
    _ADAPT_UP = 0.75
    _ADAPT_DOWN = 0.35
    _ADAPT_MIN_TICKS = 2
    _EWMA_BETA = 0.5

    def _adapt_step(self) -> int:
        """Ladder index delta the controller wants RIGHT NOW (+1 / -1 /
        0), from the live acceptance EWMA with hysteresis.  Split from
        the state change so `_can_overlap` can ask "is a step due?"
        without taking it — a chained dispatch reuses its
        predecessor's k, so while a step is due the overlap gate must
        force a real boundary or adaptation would never run for
        model-draft engines (their spec ticks chain indefinitely under
        the default overlap flag)."""
        if not self.spec_adaptive or self._accept_ewma is None \
                or self._spec_ticks_since_adapt < self._ADAPT_MIN_TICKS:
            return 0
        i = self.spec_ladder.index(self.spec_k_now)
        if self._accept_ewma >= self._ADAPT_UP \
                and i + 1 < len(self.spec_ladder):
            return 1
        if self._accept_ewma <= self._ADAPT_DOWN and i > 0:
            return -1
        return 0

    def _adapt_k(self) -> int:
        """Boundary-time adaptive-k step: move ``spec_k_now`` one rung
        along the ladder per decision, driven by the live acceptance
        EWMA (the same counters `stats()['speculative']` reports).
        Every rung's program is warmed, so a step never compiles."""
        step = self._adapt_step()
        if step:
            i = self.spec_ladder.index(self.spec_k_now)
            self.spec_k_now = self.spec_ladder[i + step]
            self.spec_k_switches += 1
            self._spec_ticks_since_adapt = 0
        return self.spec_k_now

    def _dispatch_spec(self, active, t0, chain=None):
        """Launch one speculative tick (proposal + verify) in flight.

        Proposals and verify both write positions ``seq..seq+k-1``;
        only the accepted prefix becomes durable — the rest is masked
        by seq_lens and overwritten by the next chunk (rollback by
        construction).  PER-SLOT eligibility: each slot's emit cap
        ``kcap = min(k, remaining budget)`` rides in as a device input;
        host seq_lens/tok_pos advance by that per-slot upper bound now
        (budget clamps and a chained dispatch's block coverage need a
        bound, not the truth) and harvest refunds the per-slot
        shortfall ``kcap - emitted``.  A chained MODEL-draft dispatch
        feeds the predecessor's on-device new_lens/new_last handles —
        the draft phase of tick t+1 runs in tick t's harvest bubble.
        Host-draft (ngram) ticks never chain: the next proposal needs
        the harvested tokens.  With ``FLAGS_serving_spec_adaptive`` an
        unchained dispatch first lets the controller step k along the
        warmed ladder."""
        k = chain.k if chain is not None else self._adapt_k()
        kcap = np.zeros((self.B,), np.int32)
        ineligible = 0
        for slot in active:
            req = self.slot_req[slot]
            cap = min(k, req.max_new_tokens - int(self.tok_pos[slot]))
            kcap[slot] = cap       # >= 1: eligibility/overlap gated it
            if cap < k:
                ineligible += 1
            base = int(self.seq_lens[slot])
            self._draw_blocks(slot, base, base + cap)
        if ineligible:
            self.spec_ineligible_slots += ineligible
            _M_SPEC_INELIGIBLE.inc(ineligible)
        _M_SPEC_K.set(k)
        san = _jaxsan.token("serving.tick")

        def staged(dtoks=None):
            with self._staging("serve:tick_stage",
                               partial(_jaxsan.shield, san)) as dev:
                if chain is not None:
                    lens_in, last_in = chain.new_lens, chain.new_last
                else:
                    lens_in, last_in = (dev(self.seq_lens),
                                        dev(self.last_tok))
                samp = (dev(self.samp_do), dev(self.samp_temp),
                        dev(self.samp_topk), dev(self.samp_topp),
                        dev(self.samp_seed))
                args = [dev(self.tables), lens_in, last_in]
                if dtoks is not None:
                    args.append(dev(dtoks))
                return [*args, *samp, dev(kcap)]

        with self._params_for_call() as param_vals, \
                _flight.guard("serving.tick"):
            if self.spec_model:
                toks, counts, accepts, new_lens, new_last, self.pools, \
                    self.dpools = self._dispatch_call(
                        "serving.tick.dispatch",
                        lambda: self._spec_program(k)(
                            param_vals, self._draft_vals(), self.pools,
                            self.dpools, *staged()))
                self.steps += k + 1      # k draft forwards + one verify
            else:
                # host-side n-gram proposals (near-zero cost; the whole
                # draft "model" is a few dict probes per slot) ride in
                # as device inputs — the program is one verify forward
                dtoks = np.zeros((self.B, k), np.int32)
                for slot in active:
                    req = self.slot_req[slot]
                    if req._drafter is None:
                        from .drafting import NGramDraft
                        req._drafter = NGramDraft()
                    dtoks[slot] = req._drafter.propose_stream(
                        req.prompt_ids, req.output_ids, k)
                toks, counts, accepts, new_lens, new_last, self.pools \
                    = self._dispatch_call(
                        "serving.tick.dispatch",
                        lambda: self._spec_hd_program(k)(
                            param_vals, self.pools, *staged(dtoks)))
                self.steps += 1          # one chunk verify forward
        for slot in active:
            self.seq_lens[slot] += int(kcap[slot])
            self.tok_pos[slot] += int(kcap[slot])
        pend = _PendingTick(active=active, k=k, toks=toks, logits=None,
                            reqs=list(self.slot_req), t0=t0,
                            device_sampling=True, step_no=self.steps,
                            san=san)
        pend.spec = True
        pend.counts = counts
        pend.accepts = accepts
        pend.new_lens = new_lens
        pend.new_last = new_last
        pend.kcap = kcap
        return pend

    def _dispatch_mtp(self, active, t0, chain=None):
        """Launch one self-drafted tick (`_mtp_tick_program`) in flight.
        As a spec tick (`_dispatch_spec`): each slot's emit cap ``kcap =
        min(2, remaining budget)`` rides in, the host's lengths advance
        by that upper bound now and the harvest refunds ``kcap -
        emitted``; a chained dispatch takes the predecessor's lengths,
        last tokens AND drafts from the device, so nothing the next tick
        needs comes back to the host first."""
        kcap = np.zeros((self.B,), np.int32)
        for slot in active:
            req = self.slot_req[slot]
            cap = min(2, req.max_new_tokens - int(self.tok_pos[slot]))
            kcap[slot] = cap
            base = int(self.seq_lens[slot])
            end = len(req.prompt_ids) + req.max_new_tokens
            # the model writes positions base..base+cap-1, the module the
            # slots one further
            self._draw_blocks(slot, base, min(base + cap + 1, end))
        san = _jaxsan.token("serving.tick")

        def launch():
            with self._staging("serve:tick_stage",
                               partial(_jaxsan.shield, san)) as dev:
                if chain is not None:
                    carry = (chain.new_lens, chain.new_last,
                             chain.new_draft)
                else:
                    carry = (dev(self.seq_lens), dev(self.last_tok),
                             dev(self.draft_tok))
                args = [dev(self.tables), *carry, dev(kcap)]
            return self._mtp_tick_program()(param_vals, self.pools, *args)

        with self._params_for_call() as param_vals, \
                _flight.guard("serving.tick"):
            (toks, counts, accepts, new_lens, new_last, new_draft, judged,
             self.pools, state) = self._dispatch_call(
                "serving.tick.dispatch", launch)
        self.steps += 1              # one verify forward (and its draft)
        # the verify writes both positions of every running slot
        self._count_ring(2 * len(active))
        for slot in active:
            self.seq_lens[slot] += int(kcap[slot])
            self.tok_pos[slot] += int(kcap[slot])
        # k = 1: the drafts a slot proposes a tick (the harvest's count)
        pend = _PendingTick(active=active, k=1, toks=toks, logits=None,
                            reqs=list(self.slot_req), t0=t0,
                            device_sampling=True, step_no=self.steps,
                            san=san)
        pend.spec = True
        pend.counts, pend.accepts = counts, accepts
        pend.new_lens, pend.new_last = new_lens, new_last
        pend.new_draft, pend.judged = new_draft, judged
        pend.kcap = kcap
        pend.state = state
        return pend

    def _readback(self, pend) -> dict:
        """Every device-to-host read of a harvested tick beyond its
        tokens, by name, in one stretch under ``serve:readback`` at the
        head of ``serve:emit`` (no span where there is nothing to read):
        the cache's per-layer state rows (``("state", i)``); a spec or
        self-drafted tick's ``counts``, ``accepts``, ``judged``,
        ``new_draft``; a block tick's ``step_of``; the host-sampling fallback's
        ``logits`` where a row is sampled or screened.  The tokens are
        here, so the program is done and none of them is waited for:
        each is a device-to-host copy and a wake-up."""
        want = {("state", i): a for i, a in enumerate(pend.state)}
        if pend.spec:
            want["counts"], want["accepts"] = pend.counts, pend.accepts
            if pend.judged is not None:
                want["judged"] = pend.judged
                want["new_draft"] = pend.new_draft
        elif pend.block is not None:
            want["step_of"] = pend.block[2]
        elif pend.logits is not None and (
                self._screens_decode_logits() or any(
                    pend.reqs[s].do_sample and not pend.reqs[s].done
                    for s in pend.active)):
            want["logits"] = pend.logits
        if not want:
            return want
        with _span("serve:readback", arrays=len(want)) as sp:
            host = {k: np.asarray(a) for k, a in want.items()}
            sp.set(bytes=sum(a.nbytes for a in host.values()))
        return host

    def _harvest_tick(self, pend) -> None:
        """Block on the tick's device tokens and feed the requests:
        append, EOS/budget-check, host-sample (fallback path only).
        `pend.reqs` is the slot->request snapshot from dispatch time —
        under overlap a request may have finished (EOS) while its next
        tick was already in flight; its overrun rows are discarded."""
        k = pend.k
        # harvest-wait phase: the block below is where device compute
        # not yet finished is actually waited for
        with _span("serve:harvest_wait") as sp_wait, \
                _flight.guard("serving.tick"):
            # first host block on the async result: a decode-execution
            # error (OOM, XlaRuntimeError) surfaces HERE, not at the
            # guarded dispatch — keep the post-mortem dump coverage.
            # The tick watchdog (FLAGS_serving_tick_timeout_s) bounds
            # this block: a hung device program raises TickTimeout
            # instead of wedging the loop forever.
            toks = self._materialize(pend.toks)
        # emit phase, to t_done: read back, append, sample, stream (a
        # harvest that raises abandons the span, which then records
        # nothing)
        sp_emit = _span("serve:emit").begin()
        host = self._readback(pend)
        if pend.state:
            self._cache_state = (pend.step_no, [
                host["state", i] for i in range(len(pend.state))])
        # the program has materialized: every host buffer fed at dispatch
        # must still hash to its dispatch-time checksum (jaxsan; no-op
        # unless FLAGS_enable_jaxsan)
        _jaxsan.verify(pend.san)
        logits_np = host.get("logits")
        bad_slots: dict = {}
        if not pend.spec and pend.logits is not None:
            # host-sampling decode path: the per-row logits are host-
            # visible, so NaN attribution is PER SLOT here — an armed
            # chaos injection or a real non-finite forward implicates
            # exactly one row (evicted outcome=error after the loop)
            logits_np, bad_slots = self._screen_decode_logits(
                pend, logits_np)
        toks_before = self.tokens_out
        sampled = 0
        spec_accepted = 0
        spec_proposed = 0
        harvested_by: List = []   # (req, tokens harvested this tick)
        if pend.spec:
            # speculative tick: per-slot emitted counts (1..kcap) and
            # accepted-draft counts materialize with the tokens; refund
            # the dispatch-time PER-SLOT upper-bound advance (kcap per
            # slot) down to the true emitted length — relative, so it
            # composes with any further conservative advance already
            # applied by an overlapped next dispatch
            counts, accepts = host["counts"], host["accepts"]
            mtp = pend.judged is not None
            if mtp:
                judged, new_draft = host["judged"], host["new_draft"]
            metrics_on = _metrics.enabled()
            for slot in pend.active:
                req = pend.reqs[slot]
                c = int(counts[slot])
                cap = int(pend.kcap[slot])
                self.seq_lens[slot] -= cap - c
                self.tok_pos[slot] -= cap - c
                if req.done:
                    continue     # whole row is EOS overrun
                n_before = len(req.output_ids)
                harvested_by.append((req, n_before))
                req._ticks += 1
                # acceptance accounts the full k proposals (the
                # drafter-quality signal the adaptive controller
                # consumes), independent of the slot's emit cap
                spec_proposed += k
                spec_accepted += int(accepts[slot])
                req._spec_proposed += k
                req._spec_accepted += int(accepts[slot])
                if metrics_on:
                    _M_SPEC_SLOT_ACC.set(
                        round(req._spec_accepted
                              / max(req._spec_proposed, 1), 4),
                        slot=slot)
                self.last_tok[slot] = int(toks[slot, c - 1])
                if mtp:
                    self.draft_tok[slot] = int(new_draft[slot])
                    req.draft_log.append((int(judged[slot]),
                                          bool(accepts[slot]), c))
                for j in range(c):
                    if req.done:
                        break    # post-eos tokens are discarded
                    tok = int(toks[slot, j])
                    if req.do_sample:
                        sampled += 1
                    req.output_ids.append(tok)
                    req._stream_push(tok)
                    self.tokens_out += 1
                    self._maybe_finish(req, tok)
            self.spec_ticks += 1
            self.spec_proposed += spec_proposed
            self.spec_accepted += spec_accepted
            if mtp and pend.state:
                # the device-side counts came with the tick's tokens (the
                # module's layer holds them): what they grew by since the
                # last harvest feeds the counters
                drafted, taken = (
                    int(v) for v in self.cache_state()["mtp"][-1])
                _M_MTP_DRAFTED.inc(drafted - self.mtp_forwards)
                _M_MTP_ACCEPTED.inc(taken - self.mtp_accepted)
                self.mtp_forwards, self.mtp_accepted = drafted, taken
            if spec_proposed:
                _M_SPEC_PROPOSED.inc(spec_proposed)
                # the adaptive controller's evidence: tick-level accept
                # rate folded into a fast EWMA (consulted at boundary
                # dispatches by `_adapt_k`)
                rate = spec_accepted / spec_proposed
                self._accept_ewma = rate if self._accept_ewma is None \
                    else (self._EWMA_BETA * self._accept_ewma
                          + (1.0 - self._EWMA_BETA) * rate)
                self._spec_ticks_since_adapt += 1
            if spec_accepted:
                _M_SPEC_ACCEPTED.inc(spec_accepted)
        elif pend.block is not None:
            # block-diffusion tick: each slot's block arrives whole; its
            # new tokens (behind the prompt's tail, within the budget) are
            # handed over together, each with the forward that revealed it
            given, new, _ = pend.block
            step_of = host["step_of"]
            per = self.gen.block_length // self.gen.denoising_steps
            _M_BLOCK_FORWARDS.inc(k)
            for slot in pend.active:
                req = pend.reqs[slot]
                masks = self.gen.block_length - int(given[slot])
                if masks >= per:
                    _M_BLOCK_REVEALED.inc((masks // per) * per,
                                          per_forward=per)
                if masks % per:
                    _M_BLOCK_REVEALED.inc(masks % per,
                                          per_forward=masks % per)
                if req.done:
                    continue
                req._ticks += 1
                if req._t_first is None:
                    # its first tokens: a TTFT, and no inter-token gap
                    self._note_first_token(req, time.perf_counter())
                else:
                    harvested_by.append((req, len(req.output_ids)))
                lo = int(given[slot])
                for j in range(lo, lo + int(new[slot])):
                    if req.done:
                        break    # tokens behind an eos are dropped
                    tok = int(toks[slot, j])
                    req.output_ids.append(tok)
                    req.reveal_steps.append(int(step_of[slot, j]))
                    req._stream_push(tok)
                    self.tokens_out += 1
                    self._maybe_finish(req, tok)
        else:
            for slot in pend.active:
                req = pend.reqs[slot]
                if req.done:
                    continue     # whole row is EOS overrun
                if slot in bad_slots:
                    continue     # non-finite row: no tokens emitted;
                                 # the slot is evicted outcome=error
                                 # at the end of this harvest
                n_before = len(req.output_ids)
                harvested_by.append((req, n_before))
                req._ticks += 1
                self.last_tok[slot] = int(toks[slot, -1])
                for j in range(k):
                    if req.done:
                        break    # post-eos tokens are discarded (the
                                 # compiled tick keeps decoding; the
                                 # cache rows die with the eviction)
                    if req.do_sample and not pend.device_sampling:
                        tok = req._sample(logits_np[slot])
                        self.last_tok[slot] = tok
                    else:
                        tok = int(toks[slot, j])
                    if req.do_sample:
                        sampled += 1
                    req.output_ids.append(tok)
                    req._stream_push(tok)
                    self.tokens_out += 1
                    self._maybe_finish(req, tok)
        # wall time ATTRIBUTABLE to this tick: an overlapped tick was
        # dispatched before the previous harvest finished, so clock it
        # from that harvest, not from its own dispatch — tick_seconds
        # then sum to real elapsed wall and tokens/sec stays honest
        t_done = time.perf_counter()
        t_from = pend.t0 if self._last_harvest_t is None \
            else max(pend.t0, self._last_harvest_t)
        self._last_harvest_t = t_done
        dt = t_done - t_from
        harvested = self.tokens_out - toks_before
        sp_emit.set(tokens=harvested)
        sp_emit.end()
        if harvested > 0 and dt > 0:
            # always-on tick-level TPOT evidence for the fleet telescope
            # (one harvest gap imputed to the k tokens it yielded) —
            # deliberately NOT per-request timing, so the "metrics off
            # = zero per-request tracing work" pin stays intact
            self._ev_tpot.add(
                dt / max(k if pend.block is None
                         else self.gen.block_length, 1), weight=harvested)
        # per-token inter-token latency (TPOT): tokens arrive k at a
        # time, so each of this harvest's tokens is imputed an equal
        # share of the gap since the request's previous token
        sketch = _metrics.enabled()
        tpot_slo = _flags.get_flag("serving_tpot_slo_ms") if sketch else 0
        for req, n_before in harvested_by:
            n_new = len(req.output_ids) - n_before
            if n_new <= 0:
                continue
            gap = (t_done - req._t_last) / n_new
            req._t_last = t_done
            if sketch:
                _M_TPOT.observe(gap, weight=n_new)
                if tpot_slo > 0 and gap * 1e3 > tpot_slo:
                    _M_SLO.inc(n_new, metric="tpot")
        self.ticks += 1
        _M_TICKS.inc()
        _M_TICK_S.observe(dt)
        _M_TOKENS.inc(harvested)
        if sampled:
            _M_SAMPLED.inc(sampled)
        if dt > 0:
            _M_TPS.set(round(harvested / dt, 1))
        self._update_occupancy()
        if _metrics.enabled():
            # the flight ring keeps the last-K ticks, so a post-mortem
            # dump of a wedged/crashed engine shows what was in flight
            # per-tick phase breakdown: the seconds of this tick's
            # serve:* spans, each phase timed once.  The phases need not
            # sum to wall_s: an overlapped tick's wall clock starts at
            # the previous harvest, and device compute overlaps the host
            # phases by design.
            wait_s, emit_s = sp_wait.seconds, sp_emit.seconds
            rec = {
                "timeline": "serving", "step": pend.step_no,
                "t_unix": round(time.time(), 6),
                "wall_s": round(dt, 6), "decode_steps": k,
                "tokens": harvested, "overlap": pend.overlapped,
                "tokens_per_sec": round(harvested / dt, 1) if dt else 0.0,
                "active": len(pend.active), "waiting": len(self.waiting),
                "free_blocks": self._free_capacity(),
                "phases": {
                    "schedule_ms": round(pend.sched_s * 1e3, 4),
                    "chunk_prefill_ms": round(pend.chunk_s * 1e3, 4),
                    "dispatch_ms": round(pend.dispatch_s * 1e3, 4),
                    "harvest_wait_ms": round(wait_s * 1e3, 4),
                    "emit_ms": round(emit_s * 1e3, 4),
                    "host_ms": round((pend.sched_s + pend.chunk_s
                                      + pend.dispatch_s + emit_s)
                                     * 1e3, 4),
                    "device_wait_ms": round(wait_s * 1e3, 4)}}
            if pend.spec:
                rec["spec"] = True
                rec["spec_kind"] = self.spec_kind
                rec["spec_k"] = pend.k
                rec["spec_accepted"] = spec_accepted
            if pend.chunks:
                rec["prefill_chunks"] = pend.chunks
            tids = sorted({r.trace_id for r, _ in harvested_by
                           if r.trace_id})
            if tids:
                rec["trace_ids"] = tids
            self._flightrec().record_step(rec)
        # failure isolation (ISSUE 15): rows whose logits screened
        # non-finite are evicted HERE — outcome=error, blocks released
        # through the single accounting path — and every other slot's
        # stream is untouched (their tokens were already emitted above)
        for slot, err in bad_slots.items():
            self._error_evict(slot, err)
        # blocksan boundary reconciliation: the harvest is the one point
        # where no admission is mid-flight and every transient pin has
        # resolved — ledger vs tables/shadow rows/index, free-list
        # agreement, registered-block checksums (no-op when disarmed)
        _jaxsan.blocksan_verify(self)

    def _tick_size(self, active) -> int:
        """Steps this tick may batch: bounded by the configured tick
        size and every active request's remaining budget (over-decoding
        past a budget would outrun its block reservation).  Budgets
        count DISPATCHED tokens (`tok_pos`), so an overlapped in-flight
        tick is already accounted for.  With on-device sampling,
        sampled and greedy rows share the full k-step tick; the
        host-sampling fallback (FLAGS_serving_device_sampling=0)
        demotes any tick with a sampling request to k=1."""
        k = self.steps_per_tick
        device_sampling = _flags.get_flag("serving_device_sampling")
        for slot in active:
            req = self.slot_req[slot]
            if req.do_sample and not device_sampling:
                return 1
            k = min(k, req.max_new_tokens - int(self.tok_pos[slot]))
        # exactly two compiled variants: the full tick and the k=1 tail
        # (a mid-run compile of an intermediate size costs more than the
        # single steps it would save)
        return k if k >= self.steps_per_tick else 1

    def _can_overlap(self, pend) -> bool:
        """May tick t+1 dispatch before tick t (`pend`) is harvested?
        Where `_boundary_reason` names no reason for a boundary."""
        return self._boundary_reason(pend) is None

    def _boundary_reason(self, pend) -> Optional[str]:
        """Why tick t+1 may NOT dispatch before tick t (`pend`) is
        harvested, in one word, or None where it may (the chain).
        Chaining requires the overlap flag, next-token choice living on
        device (host sampling owns it otherwise), no admissions pending
        (they join at a REAL boundary: their prefill must not race the
        in-flight tick's pool writes), and at least one budgeted token
        per active request beyond the in-flight tick (the block-budget
        clamp that keeps EOS overrun inside the reservation).  The
        chained dispatch continues `pend`'s KIND: a spec tick chains a
        spec tick (on the device seq_lens/last handles, needing spec_k
        budget beyond the in-flight upper bound), a plain tick a plain
        one — a kind switch is a real boundary (harvest first).

        A chained dispatch skips `_boundary_schedule`, and behind a
        front end long answers can chain for seconds, so everything
        only a boundary acts on is a reason here: a waiting request, a
        cancelled one in any slot, a requested drain, the serve loop's
        stop event.  The tick in flight is then harvested and the next
        dispatch is a real boundary — within one tick, whatever runs.

        The word goes on that boundary's ``serve:schedule`` span as
        ``why`` and into the counter ``serving.boundaries``; the spec
        and the plain branch share theirs."""
        if not _flags.get_flag("serving_overlap"):
            return "overlap_off"
        if self.gen is not None:
            return "block_tick"  # harvested before the next is launched
        if self._drain_requested or (self._stop_event is not None
                                     and self._stop_event.is_set()):
            return "stopping"    # the loop is ending: harvest what flies
        if self.waiting:
            return "waiting"     # admissions join at a real boundary
        if any(r is not None and r.cancelled for r in self.slot_req):
            return "cancelled"   # evictions and aborts: a boundary's
        if self.prefilling and not self._chunk_overlap_ok():
            return "chunk_pending"   # chunk work only a boundary may do
        device_sampling = _flags.get_flag("serving_device_sampling")
        if pend.spec:
            if not self.spec_model and self.mtp is None:
                return "host_draft"  # ngram proposals need the harvested
                                     # tokens: a host draft cannot chain
            if self._adapt_step():
                return "adapt_k"     # a k step is due: chained dispatches
                                     # reuse chain.k, so force a boundary
                                     # and let _adapt_k move the rung
            if not device_sampling:
                return "host_sampling"   # mid-run flip: verify owns it
        else:
            if not pend.device_sampling and any(
                    pend.reqs[s].do_sample for s in pend.active):
                return "host_sampling"
            if self.spec and self._spec_eligible(pend.active,
                                                 device_sampling):
                return "kind_switch"     # plain->spec (e.g. the sampling
                                         # flag flipped back on)
        for slot in pend.active:
            req = self.slot_req[slot]
            if req is None or req.done:
                return "finished"        # eviction boundary needed first
            if req.max_new_tokens - int(self.tok_pos[slot]) < 1:
                return "budget_spent"    # the in-flight tick exhausts the
                                         # budget (per-slot caps need >= 1)
        # X-ray sampling contract (ISSUE 14): a due synced probe must
        # land on a REAL boundary — a chained dispatch feeds the
        # predecessor's device handles, so a probe around it would time
        # both ticks; the program a chained dispatch would run must not
        # be due one
        if _xray.sampling_on():
            if pend.spec:
                nxt = self._mtp_fn if self.mtp is not None \
                    else self._spec_fns.get(pend.k)
            else:
                k = self._tick_size(pend.active)
                nxt = self._decode_fn if (k == 1 and not device_sampling) \
                    else self._tick_fns.get(k)
            if _xray.sample_due(nxt):
                return "xray_probe"
        return None

    def _chunk_overlap_ok(self) -> bool:
        """May pending chunk-prefill work ride BEHIND an overlapped
        tick instead of forcing a real boundary (the parked PR 11
        remainder, ``FLAGS_serving_chunk_overlap``)?  Only NON-FINAL
        chunks qualify: the final chunk host-syncs its logits row
        (`_screen_row`) and installs the shadow table row — boundary
        work by contract.  So the head chunked admission must still
        have more than one chunk of prompt left."""
        if self.chunk <= 0 \
                or not _flags.get_flag("serving_chunk_overlap"):
            return False
        req = self.prefilling[0]
        return len(req.prompt_ids) - req._chunk_off > self.chunk

    def _overlap_chunk_work(self, nxt) -> None:
        """Dispatch non-final prefill chunks for the head chunked
        admission BEHIND the just-chained tick ``nxt``: programs
        serialize in dispatch order on the device stream and each chunk
        consumes ``self.pools`` — by now the chained tick's output
        handle — so the chunk reads post-tick pool state exactly as a
        boundary dispatch would, while its host-side enqueue cost hides
        under the in-flight ticks.  Chunk writes land in the admission's
        own (not-yet-decodable) blocks, disjoint from every active
        slot's, so tick/chunk order commutes and token streams stay
        bit-identical with the flag off.  The FINAL chunk never runs
        here (see `_chunk_overlap_ok`); an armed X-ray sampler skips
        the path entirely — a synced probe around a chunk program
        would time the chained tick too."""
        if not self.prefilling or not self._chunk_overlap_ok() \
                or _xray.sampling_on():
            return
        budget = max(1, int(_flags.get_flag(
            "serving_prefill_chunks_per_tick")))
        req = self.prefilling[0]
        self._chunks_this_boundary = 0
        self._chunk_s_this_boundary = 0.0
        spent = 0
        while (spent < budget
               and len(req.prompt_ids) - req._chunk_off > self.chunk):
            self._prefill_chunk_step(req)
            spent += 1
            self.overlap_chunks_total += 1
        # fold the accounting into the chained tick's record: these
        # chunks belong to ITS dispatch window, not the next boundary's
        nxt.chunks += self._chunks_this_boundary
        nxt.chunk_s += self._chunk_s_this_boundary
        self._chunks_this_boundary = 0
        self._chunk_s_this_boundary = 0.0

    def _cycle(self, pend):
        """One turn of the serve loop, the one both drivers run: takes
        the tick in flight (None at a boundary) and returns the one in
        flight afterwards.  With nothing in flight it dispatches a
        boundary tick (schedule first).  Then, where `_boundary_reason`
        names none, tick t+1 is chained on `pend`'s device tokens — with
        the non-final prefill chunks that may ride behind it — BEFORE
        `pend` is harvested; otherwise `pend` is harvested alone, the
        reason is kept for the next ``serve:schedule`` span (``why``)
        and the next turn starts at a real boundary.  Crash-only: a failure
        is absorbed by `_absorb_failure` (request strike, or eviction
        of the slots the ticks in flight covered) and the turn ends
        with nothing in flight; only sanitizer findings propagate."""
        if pend is None:
            try:
                pend = self._dispatch_tick(boundary=True)
            except Exception as e:  # noqa: BLE001 - crash-only guard
                if not self._absorb_failure(e, ()):
                    raise
                return None
            if pend is None:
                return None      # nothing decodable yet (chunks, queue)
        nxt = None
        try:
            why = self._boundary_reason(pend)
            if why is not None:
                self._boundary_why = why
            else:
                nxt = self._dispatch_tick(boundary=False, chain=pend)
                if nxt is None:
                    self._boundary_why = "nothing_to_chain"
                else:
                    nxt.overlapped = True
                    _M_OVERLAP.inc()
                    try:
                        self._overlap_chunk_work(nxt)
                    except Exception as e:  # noqa: BLE001
                        # a chunk's own failure strikes ITS request; the
                        # two ticks in flight are sound and are harvested
                        if getattr(e, "_serving_req", None) is None \
                                or not self._absorb_failure(e, ()):
                            raise
            self._harvest_tick(pend)
        except Exception as e:  # noqa: BLE001 - crash-only guard
            if not self._absorb_failure(e, (pend, nxt)):
                raise
            return None
        return nxt

    def _has_work(self) -> bool:
        return bool(self.waiting or self.prefilling
                    or self._active_slots())

    def run(self) -> List[Request]:
        """Drive `_cycle` until every queued request finishes; returns
        them in completion order.  The loop keeps one tick in flight
        (``FLAGS_serving_overlap``): dispatch t+1 (chaining t's device
        last-token column), THEN harvest t — device compute and host
        harvest/detokenize overlap instead of strictly alternating."""
        from ..observability import http as _http
        _http.start_from_flags()   # no-op unless FLAGS_metrics_port > 0
        _http.attach_engine(self)
        _http.start_serving_from_flags()   # FLAGS_serving_http_port
        if self._warmup_info is None \
                and _flags.get_flag("serving_warmup"):
            self.warmup()          # compile the whole grid BEFORE
        self._mark_ready()         # traffic waits on a program build
        pend = None
        while pend is not None or self._has_work():
            pend = self._cycle(pend)
        # final eviction sweep
        for slot in list(range(self.B)):
            if self.slot_req[slot] is not None and self.slot_req[slot].done:
                self._evict(slot)
        # drained-engine invariant: nothing leaked — every block is
        # free or held only by the prefix index (no-op when disarmed)
        _jaxsan.blocksan_verify(self)
        return self.finished

    _IDLE_SPAN_S = 0.1     # the longest one `serve:idle` span lasts

    def serve_forever(self, stop_event, idle_s: float = 0.002) -> None:
        """Drive `_cycle` until ``stop_event`` (a threading.Event) is
        set, serving traffic submitted concurrently — the loop behind
        the streaming endpoint (``FLAGS_serving_http_port``), a fleet
        replica and every serve cell of the benchmark: handler threads
        `add_request` and read each request's token stream; this loop
        ticks while work exists and naps (``idle_s``; one span
        ``serve:idle`` an idle period) otherwise.

        The same cycle as `run()`: while nothing needs a boundary, tick
        t+1 is enqueued on tick t's device tokens before t is harvested,
        so the device does not wait for the host between ticks.  An
        arrival, a cancellation, a finished or nearly spent request, a
        drain request or the stop event ends the chain (`_can_overlap`):
        the tick in flight is harvested and the next dispatch is a real
        boundary, so a latency-facing front end sees its admissions and
        cancellations at most one tick later than a synchronous loop
        would show them.  The tick in flight is always harvested before
        `drain()` runs and before this returns: no token of a request
        the engine took is dropped.

        Crash-only (ISSUE 15): every cycle runs under the tick guard —
        one request's failure never kills the loop — and SIGTERM (main
        thread only) or ``POST /drain`` flips `request_drain()`, which
        this loop turns into a graceful `drain()` and a clean return."""
        import signal as _signal
        from ..observability import http as _http
        _http.start_from_flags()
        _http.attach_engine(self)
        _http.start_serving_from_flags()
        old_handler = None
        try:
            old_handler = _signal.signal(
                _signal.SIGTERM,
                lambda signum, frame: self.request_drain())
        except ValueError:
            pass    # not the main thread: POST /drain still works
        self._stop_event = stop_event
        try:
            if self._warmup_info is None \
                    and _flags.get_flag("serving_warmup"):
                self.warmup()
            self._mark_ready()
            pend = None
            while not stop_event.is_set():
                if pend is None and self._drain_requested \
                        and not self._draining:
                    self.drain()
                    return
                if pend is not None or self._has_work():
                    pend = self._cycle(pend)
                else:
                    # an empty engine is not a slow one: ONE span an idle
                    # period, to the arrival, stop or drain that ends it
                    # (or _IDLE_SPAN_S, so that a profiler that starts
                    # inside a long period loses no more of it)
                    with _span("serve:idle"):
                        t_end = time.perf_counter() + self._IDLE_SPAN_S
                        while True:
                            time.sleep(idle_s)
                            if self._has_work() or stop_event.is_set() \
                                    or self._drain_requested \
                                    or time.perf_counter() >= t_end:
                                break
            if pend is not None:
                # stopped with a tick in flight: the set event forbids a
                # chain, so this turn harvests it and leaves none
                self._cycle(pend)
        finally:
            self._stop_event = None
            if old_handler is not None:
                try:
                    _signal.signal(_signal.SIGTERM, old_handler)
                except ValueError:
                    pass

    # -------------------------------------- graceful drain (ISSUE 15)
    def request_drain(self) -> None:
        """Ask the engine to drain at its next boundary.  A bare bool
        store — safe from signal handlers and the POST /drain handler
        threads.  Admission closes immediately (`add_request` rejects,
        /healthz answers 503 draining); the engine loop performs the
        actual drain."""
        self._drain_requested = True

    def drain(self, deadline_s: Optional[float] = None) -> dict:
        """Graceful drain: flip admission off, cancel the waiting queue
        (``outcome=drained`` — their SSE streams end in an error
        frame), keep ticking under the crash-only guard until every
        in-flight request finishes or ``deadline_s``
        (``FLAGS_serving_drain_timeout_s``) expires, evict stragglers
        ``outcome=drained``, blocksan-verify the emptied ledger, then
        export the prefix cache when ``FLAGS_serving_prefix_export_dir``
        is set.  Idempotent per engine; returns (and stashes for
        ``stats()``/``health()``) the drain report."""
        if self._drain_info is not None:
            return self._drain_info
        if deadline_s is None:
            deadline_s = float(_flags.get_flag("serving_drain_timeout_s"))
        self._drain_requested = True
        self._draining = True
        t0 = time.monotonic()
        self._flightrec().record_event(
            "drain_start", waiting=len(self.waiting),
            running=self.B - len(self.free_slots))
        # the waiting queue was never admitted: hand it back NOW with a
        # terminal reason the client can retry on (another replica owns
        # the retry — this engine is going away)
        cancelled = 0
        for r in list(self.waiting):
            self._terminal_trace(r, "drained")
            self.finished.append(r)
            r._stream_push(None)
            cancelled += 1
        self.waiting.clear()
        self._update_pressure()
        # finish in-flight work (chunked prefills included: their
        # prompts already consumed compute) up to the deadline
        deadline = t0 + max(float(deadline_s), 0.0)
        while (self.prefilling or self._active_slots()) \
                and time.monotonic() < deadline:
            self._guarded_step()
        # deadline stragglers: evict with outcome=drained (their
        # partial streams end in an SSE error frame, blocks released)
        evicted = 0
        for slot in list(range(self.B)):
            req = self.slot_req[slot]
            if req is None:
                continue
            if req._prefilling:
                self._abort_prefill(req, outcome="drained")
                evicted += 1
            elif req.done:
                self._evict(slot)
            else:
                self._terminal_trace(req, "drained")
                self._evict(slot)
                req._stream_push(None)
                evicted += 1
        # drain-complete invariant: the ledger must reconcile to
        # empty-running — every block free or held only by the prefix
        # index (no-op unless blocksan is armed)
        _jaxsan.blocksan_verify(self)
        export = None
        export_dir = self._export_dir
        if self.prefix is not None and export_dir:
            try:
                export = self.export_prefix_cache(export_dir)
            except Exception as e:  # noqa: BLE001 - drain must finish
                export = {"error": f"{type(e).__name__}: {e}"[:200]}
                self._flightrec().record_event(
                    "prefix_export_failed", error=export["error"])
        self._drain_info = {
            "drained_s": round(time.monotonic() - t0, 4),
            "deadline_s": float(deadline_s),
            "cancelled_waiting": cancelled,
            "evicted_running": evicted,
            "export": export}
        self._flightrec().record_event(
            "drain_complete", **{k: v for k, v in
                                 self._drain_info.items()
                                 if k != "export"})
        return self._drain_info

    # ---------------------------- prefix-cache persistence (ISSUE 15)
    def _prefix_fingerprint(self) -> dict:
        """What an export's KV contents are a pure function of (besides
        the prompt tokens): pool geometry + dtype + quant mode + the
        draft-pool layout.  Import refuses a mismatch (reason=mismatch)
        — loading another geometry's bytes would be silent garbage.
        Weight EQUALITY is deliberately not fingerprinted (documented:
        restarting with different weights under the same config is the
        operator's contract, exactly like the persistent compile
        cache)."""
        cfg = self.model.cfg
        fp = {"num_layers": int(cfg.num_layers),
              "rows": [[r.name, list(r.lead), list(r.trail)]
                       for r in self.cache.rows if r.paged],
              "block_size": self.bs,
              "vocab_size": int(cfg.vocab_size),
              "dtype": str(np.dtype(
                  np.asarray(self.pools[0][0]).dtype)),
              "quant": self.quant_mode,
              "draft": bool(self.spec_model)}
        if self.spec_model:
            dcfg = self.draft.cfg
            fp["draft_layers"] = int(dcfg.num_layers)
            fp["draft_rows"] = [[r.name, list(r.lead), list(r.trail)]
                                for r in self.draft_cache.rows if r.paged]
        return fp

    def export_prefix_cache(self, root: str) -> dict:
        """Serialize the prefix-cache index + every referenced block's
        KV contents (ALL layer pools, draft pools included) as an
        atomic, integrity-checked version under ``root`` — the PR 5
        manifest machinery: ``step_<N>.tmp`` -> sha256 manifest ->
        re-hash -> rename -> ``COMPLETE`` sentinel — so a reader can
        NEVER observe a torn export.  The gather is one device->host
        pool copy + numpy slicing (no compiled gather programs: export
        runs post-warmup and must not add program signatures)."""
        from ..distributed.checkpoint import manager as _ckpt
        if self.prefix is None:
            raise ValueError("prefix cache is disabled on this engine")
        t0 = time.perf_counter()
        index = self.prefix.export_state()
        blocks = sorted({e["block"] for e in index["entries"]})
        ids = np.asarray(blocks, np.int64)
        arrays = {"block_ids": ids}
        def gather(pools, rows, prefix):
            for li, layer in enumerate(pools):
                for row, p in zip(rows, layer):
                    if row.paged:
                        arrays[f"{prefix}{row.name}{li}"] = np.take(
                            np.asarray(p), ids, axis=row.block_axis)
        gather(self.pools, self.cache.rows, "")
        if self.dpools is not None:
            gather(self.dpools, self.draft_cache.rows, "d")
        index["meta"] = self._prefix_fingerprint()
        step = max(_ckpt.all_steps(root), default=0) + 1

        def write(tmp):
            with _chaos.checked_open(
                    os.path.join(tmp, "prefix_index.json"), "w") as f:
                json.dump(index, f)
            with _chaos.checked_open(
                    os.path.join(tmp, "prefix_blocks.npz"), "wb") as f:
                np.savez(f, **arrays)
            return ["prefix_index.json", "prefix_blocks.npz"]

        path = _ckpt.commit_single_rank(root, step, write)
        nbytes = sum(a.nbytes for a in arrays.values())
        info = {"step": step, "path": path,
                "entries": len(index["entries"]),
                "blocks": len(blocks), "bytes": int(nbytes),
                "export_s": round(time.perf_counter() - t0, 4)}
        self._flightrec().record_event("prefix_export", **info)
        return info

    def release_exported_prefix(self) -> int:
        """Export-side half of a KV handoff (inference/fleet/handoff.py):
        drop every index-only prefix entry so the blocks just serialized
        by :meth:`export_prefix_cache` return to the free pool — the
        importing engine now owns that KV, adopted through its own
        ``_alloc_block`` refcounts.  Entries whose block a running
        request still references are kept (releasing them frees
        nothing).  Returns blocks freed; graft-lint R011 requires every
        export+import pairing to call this on the export side."""
        if self.prefix is None:
            return 0
        freed = self.prefix.evict(
            self.num_blocks, self._release_block,
            lambda b: int(self.block_rc[b]) == 1)
        _jaxsan.blocksan_verify(self)
        self._flightrec().record_event(
            "prefix_handoff_release", blocks=freed)
        return freed

    def _import_prefix_cache(self, root: str) -> None:
        """Construction-time warm restart: walk export versions newest
        first, skip anything that fails manifest validation or does not
        match this engine's fingerprint (counted on
        ``serving.prefix_import_skipped_corrupt`` + a flight event —
        NEVER loaded), and rebuild the index from the first valid one:
        every entry re-pins a freshly allocated block through
        ``_alloc_block`` (rc==1 ≡ one index reference; blocksan's
        ledger sees every draw) and the exported KV bytes are installed
        into the zero-initialized pools with plain numpy + one
        device_put per pool array."""
        from ..distributed.checkpoint import manager as _ckpt
        skipped = 0
        for step in reversed(_ckpt.all_steps(root)):
            path = os.path.join(root, _ckpt.step_dir(step))
            reason = _ckpt.verify_version(path)
            if reason is not None:
                skipped += 1
                _M_PREFIX_IMPORT_SKIP.inc(reason="corrupt")
                self._flightrec().record_event(
                    "prefix_import_skip", step=step, reason=reason)
                continue
            try:
                with open(os.path.join(path, "prefix_index.json")) as f:
                    index = json.load(f)
                if index.get("meta") != self._prefix_fingerprint():
                    skipped += 1
                    _M_PREFIX_IMPORT_SKIP.inc(reason="mismatch")
                    self._flightrec().record_event(
                        "prefix_import_skip", step=step,
                        reason="engine fingerprint mismatch")
                    continue
                n = self._install_prefix_export(path, index)
            except Exception as e:  # noqa: BLE001 - restart must not die
                skipped += 1
                _M_PREFIX_IMPORT_SKIP.inc(reason="unreadable")
                self._flightrec().record_event(
                    "prefix_import_skip", step=step,
                    reason=f"{type(e).__name__}: {e}"[:200])
                continue
            self._prefix_import_info = {
                "step": step, "blocks": n, "skipped_corrupt": skipped}
            if n:
                _M_PREFIX_IMPORT.inc(n)
            self._flightrec().record_event(
                "prefix_import", step=step, blocks=n, skipped=skipped)
            # checksum the imported (registered-immutable) blocks as
            # ground truth — no-op unless blocksan is armed
            _jaxsan.blocksan_snapshot(self)
            return
        if skipped:
            self._prefix_import_info = {
                "step": None, "blocks": 0, "skipped_corrupt": skipped}

    def _install_prefix_export(self, path: str, index: dict) -> int:
        """Rebuild index entries + pool contents from one validated
        export version.  Returns blocks imported."""
        data = np.load(os.path.join(path, "prefix_blocks.npz"),
                       allow_pickle=False)
        old_ids = [int(b) for b in data["block_ids"]]
        pos = {b: i for i, b in enumerate(old_ids)}
        mapping: dict = {}

        def alloc():
            if not self.free_blocks:
                return None
            return self._alloc_block()

        def assign(old, new):
            mapping[old] = new

        n = self.prefix.import_state(index, alloc, assign)
        if not mapping:
            return 0

        def install(pools, rows, prefix, sharded):
            out = []
            for li, layer in enumerate(pools):
                new_layer = []
                for row, p in zip(rows, layer):
                    if not row.paged:
                        new_layer.append(p)
                        continue
                    host = np.zeros(p.shape, np.asarray(p).dtype)
                    src = data[f"{prefix}{row.name}{li}"]
                    at = (slice(None),) * row.block_axis
                    for old, new in mapping.items():
                        host[at + (new,)] = src[at + (pos[old],)]
                    j = jnp.asarray(host)
                    if self._tp_mesh is not None:
                        from jax.sharding import (NamedSharding,
                                                  PartitionSpec)
                        from . import tp as _tp
                        spec = _tp.pool_spec() if sharded \
                            else PartitionSpec()
                        j = jax.device_put(
                            j, NamedSharding(self._tp_mesh, spec))
                    new_layer.append(j)
                out.append(tuple(new_layer))
            return out

        self.pools = install(self.pools, self.cache.rows, "", sharded=True)
        if self.dpools is not None:
            self.dpools = install(self.dpools, self.draft_cache.rows, "d",
                                  sharded=False)
        return n

    def _mark_ready(self) -> None:
        """Admission is open and (when configured) warmup has run: the
        /healthz readiness probe flips from 503 warmup to 200."""
        if not self._ready:
            self._ready = True
            self._t_serve_start = time.monotonic()

    @property
    def ready(self) -> bool:
        return self._ready

    def health(self) -> dict:
        """The /healthz readiness document (observability/http.py): 503
        `{"ready": false, "reason": "warmup"}` until run()/
        serve_forever() completed warmup and opened admission, 503
        `{"ready": false, "reason": "draining"}` (with live
        in-flight/waiting counts) once a drain was requested, then the
        warmup / queue-depth / uptime evidence.  Reads only host-side
        scheduler ints — safe from the endpoint's handler threads."""
        if not self._ready:
            return {"ready": False, "reason": "warmup"}
        if self._draining or self._drain_requested:
            running = self.B - len(self.free_slots)
            doc = {"ready": False, "reason": "draining",
                   "in_flight": running, "waiting": len(self.waiting),
                   "prefilling": len(self.prefilling)}
            if self._drain_info is not None:
                doc["drained"] = True
                doc["drained_s"] = self._drain_info["drained_s"]
            return doc
        running = self.B - len(self.free_slots)
        doc = {"ready": True, "running": running,
               "waiting": len(self.waiting),
               "queue_depth": running + len(self.waiting),
               "slots": self.B,
               "free_slots": len(self.free_slots),
               "prefilling": len(self.prefilling),
               "uptime_s": round(
                   time.monotonic() - self._t_serve_start, 3)}
        # queue-position TTFT evidence for the fleet router's shed
        # predictor (inference/fleet/router.py): recent admission rate
        # plus median observed TTFT.  Always-on host floats, not the
        # metrics-gated sketches.
        doc["ttft_evidence"] = self._ttft_evidence()
        if self._warmup_info is not None:
            doc["warmup"] = {k: self._warmup_info[k] for k in
                             ("warmup_s", "programs", "aot_programs")}
        return doc

    def _ttft_evidence(self) -> dict:
        """Admission-rate + recent-TTFT summary for /healthz: the two
        numbers a queue-position model needs to predict the TTFT a
        request would see if routed here now."""
        ev = {"admit_rate_per_s": 0.0, "ttft_p50_s": 0.0,
              "samples": len(self._ttft_recent)}
        times = list(self._admit_times)
        if len(times) >= 2:
            span = times[-1] - times[0]
            if span > 0:
                ev["admit_rate_per_s"] = round((len(times) - 1) / span, 4)
        if self._ttft_recent:
            srt = sorted(self._ttft_recent)
            ev["ttft_p50_s"] = round(srt[len(srt) // 2], 6)
        # live decode-capacity evidence (ISSUE 17): median tick-level
        # TPOT + mean finished length let the router cap a stale
        # admission rate by what the decode loop can actually drain
        if self._ev_tpot.count > 0:
            ev["tpot_p50_s"] = round(self._ev_tpot.quantile(0.5), 6)
        if self._ev_finished > 0:
            ev["avg_tokens_out"] = round(
                self._ev_finished_tokens / self._ev_finished, 3)
        return ev

    def telemetry_snapshot(self) -> dict:
        """Always-on engine evidence for the fleet federation poll
        (``/metrics/snapshot``): terminal-outcome tallies, the TTFT-SLO
        violation count, and the tick-level TPOT sketch state.  Host
        floats/ints only — independent of FLAGS_enable_metrics."""
        return {"outcomes": dict(self._ev_outcomes),
                "slo_violations_ttft": self._ev_slo_viol,
                "finished": self._ev_finished,
                "finished_tokens": self._ev_finished_tokens,
                "tpot_sketch": self._ev_tpot.to_state(),
                "ttft_evidence": self._ttft_evidence()}

    def stats(self) -> dict:
        running = self.B - len(self.free_slots)
        # blocks held ONLY by the prefix index are free capacity: the
        # allocator reclaims them on demand (index eviction), so the
        # "nothing leaked" invariant free_blocks == num_blocks holds
        # after a drained engine even with resident prefixes
        reclaimable = self.prefix.reclaimable(self.block_rc) \
            if self.prefix is not None else 0
        out = {"steps": self.steps, "ticks": self.ticks,
               "tokens_out": self.tokens_out,
               "free_blocks": len(self.free_blocks) + reclaimable,
               "reserved": self.reserved,
               "active": len(self._active_slots()),
               "running": running,
               "waiting": len(self.waiting),
               "queue_depth": running + len(self.waiting),
               "pad_buckets": list(self.pad_ladder),
               "tp_degree": self.tp,
               "prefill_chunk": self.chunk,
               "prefilling": len(self.prefilling),
               "prefill_chunks": self.prefill_chunks_total,
               "slo_sheds": self.slo_sheds,
               "tick_errors": self.tick_errors,
               "poisoned_requests": self.poisoned_requests,
               "dispatch_retries": self.dispatch_retries,
               "draining": bool(self._draining or self._drain_requested)}
        if self._drain_info is not None:
            out["drain"] = dict(self._drain_info)
        if self.spec:
            per_slot = {
                slot: round(r._spec_accepted / r._spec_proposed, 4)
                for slot, r in enumerate(self.slot_req)
                if r is not None and r._spec_proposed}
            out["speculative"] = {
                "spec_k": self.spec_k,
                "k_now": self.spec_k_now,
                "ladder": list(self.spec_ladder),
                "adaptive": self.spec_adaptive,
                "k_switches": self.spec_k_switches,
                "draft": self.spec_kind,
                "ticks": self.spec_ticks,
                "proposed_tokens": self.spec_proposed,
                "accepted_tokens": self.spec_accepted,
                "accept_rate": round(
                    self.spec_accepted / max(self.spec_proposed, 1), 4),
                "accept_ewma": (None if self._accept_ewma is None
                                else round(self._accept_ewma, 4)),
                "ineligible_slots": self.spec_ineligible_slots,
                "per_slot_accept_rate": per_slot}
        if self.mtp is not None:
            out["spec"] = {
                "draft": "mtp", "depth": self.mtp.depth,
                "ticks": self.spec_ticks,
                "drafted": self.mtp_forwards,
                "accepted": self.mtp_accepted,
                "accept_rate": round(
                    self.mtp_accepted / max(self.mtp_forwards, 1), 4)}
        if self._quant_stats is not None:
            out["quant"] = dict(self._quant_stats)
        if self.prefix is not None:
            out["prefix_cache"] = {
                "entries": len(self.prefix),
                "hits": self.prefix.hits,
                "misses": self.prefix.misses,
                "blocks_shared": self.prefix.blocks_shared,
                "evictions": self.prefix.evictions,
                "reclaimable_blocks": reclaimable,
                "hit_tokens": self.prefix.blocks_shared * self.bs}
            if self._prefix_import_info is not None:
                out["prefix_cache"]["import"] = \
                    dict(self._prefix_import_info)
        state = self.cache_state()
        if state:
            out["cache_state"] = state
        if self._ring:
            out["cache"] = self.cache_counts()
        if self._ssm:
            out["ssm"] = {"state_resets": self.ssm_resets,
                          "carried_chunks": self.ssm_carried,
                          "state_bytes": self.ssm_state_bytes}
        if self._warmup_info is not None:
            out["warmup"] = {k: self._warmup_info[k] for k in
                             ("warmup_s", "programs", "aot_programs")}
        # the engine X-ray ledger (ISSUE 14) — process-wide like the
        # compile tracker and the latency sketches below
        xr = _xray.report(top=16)
        out["xray"] = {"sample_interval": xr["sample_interval"],
                       "programs_tracked": xr["programs_tracked"],
                       "total_est_device_s": xr["total_est_device_s"],
                       "programs": xr["programs"]}
        # p50/p90/p99 straight off the streaming sketches — process-wide
        # (the sketches aggregate every engine in the process, like the
        # /metrics scrape they feed)
        lat = {}
        for key, sk in (("ttft", _M_TTFT), ("tpot", _M_TPOT),
                        ("e2e", _M_E2E), ("queue_wait", _M_QWAIT)):
            if not sk.count():
                continue
            lat[key] = {f"p{round(q * 100)}": round(sk.quantile(q), 6)
                        for q in (0.5, 0.9, 0.99)}
        if lat:
            out["latency"] = lat
        return out
