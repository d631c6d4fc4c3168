"""mtp.py — what the tick of a self-drafting model (`glm4_moe_lite`: a
multi-token-prediction module drafts, the model verifies) must read and
compute, counted from the program's spans and counters and the
configuration, whatever implements it; and the readers of the model's
scopes inside the tick program `jit_serving_mtp_tick`.

A launch runs, for every running slot, ONE verify forward of the model
over two positions (the last token and the draft) and the module's
forward over the same two: `serve:tick_dispatch` says `steps` (verify
forwards of the launch: 1), `active`, `kv_tokens` (the cached rows of the
running slots); `serve:emit` says `tokens`, what the tick handed over.
The drafter's device-side counts (drafted, accepted) and the expert
layers' row counts come to the host with each tick's tokens; the job
reads them as the trace begins and ends (`counters`: `mtp`, `moe_rows`,
`decode_steps`).

The byte and FLOP functions take plain lists and dicts so that a test can
check them by hand.  Every reader takes `(trace, counters, args)` and
returns a number, or None where there is nothing to read (no device
plane, a program without these scopes or spans — the parent of the PR
that added them — or a rehearsal).  No share of a peak is computed from
`max_batch`: only from what the spans and the device-side counts say ran.
"""

from __future__ import annotations

import re

from benchmark.reducers import program_spans, sparse_mla

ITEM = 2          # bytes of a bfloat16
MODULE = "jit_serving_mtp_tick"
POSITIONS = 2     # a verify forward's: the last token and the draft
TICK_KEYS = ("steps", "active", "kv_tokens")


# ------------------------------------------------ counts, bytes and FLOPs

def accept_pct(drafted: float, accepted: float):
    """Share of the judged drafts that were the model's own choice, %."""
    return 100.0 * accepted / drafted if drafted else None


def tokens_per_forward(ticks: list, tokens: float):
    """Tokens handed over a running slot and verify forward: 1 where no
    draft is accepted, 2 where every one is (a little under where a
    chained tick overran a finished request)."""
    rows = sum(a["steps"] * a["active"] for a in ticks)
    return tokens / rows if rows else None


def layers_of(cfg: dict) -> int:
    """Blocks a forward and its draft run: the model's and the module's."""
    return cfg["num_layers"] + cfg["num_nextn_predict_layers"]


def latent_row_bytes(ticks: list, cfg: dict) -> float:
    """Bytes of latent rows the attention of these ticks must read: every
    running context's rows and the forward's own two, `kv_lora_rank +
    qk_rope_head_dim` bf16 (1,152 B) a row, once a block (the model's
    layers and the module's) and forward: key and value are one row, and
    both queries of a slot read it once."""
    rows = sum(a["steps"] * (a["kv_tokens"] + POSITIONS * a["active"])
               for a in ticks)
    return float(rows) * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * ITEM * layers_of(cfg)


def attention_params(cfg: dict) -> int:
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    dc, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    return (H * ql + ql * nh * (dn + dr) + H * (dc + dr)
            + dc * nh * (dn + dv) + nh * dv * H)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_weight_bytes(cfg: dict) -> float:
    """Bytes of the weights a verify forward and its draft read whatever
    the routing: every block's attention projections; the dense layers'
    MLP; every MoE block's router and shared expert; the module's
    projection; and the head TWICE (the verify's logits choose the token
    the draft is made of, so the two uses cannot share a read of 634 MB).
    Norm vectors and the embedding rows looked up are left out."""
    H = cfg["hidden_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = layers_of(cfg) - n_dense
    return float(
        layers_of(cfg) * attention_params(cfg)
        + n_dense * 3 * H * cfg["intermediate_size"]
        + n_moe * (H * cfg["n_routed_experts"]
                   + expert_params(cfg) * cfg["n_shared_experts"])
        + cfg["num_nextn_predict_layers"] * 2 * H * H
        + 2 * H * cfg["vocab_size"]) * ITEM


def forward_weight_bytes(hits: float, cfg: dict) -> float:
    """... and with the `hits` experts (summed over the MoE blocks) that
    were given at least one row."""
    return dense_weight_bytes(cfg) + float(hits) * expert_params(cfg) * ITEM


def token_flops(tokens: float, pairs: float, cfg: dict,
                head_tokens: float = None) -> float:
    """Model FLOPs of `tokens` tokens through the MODEL's layers (not the
    module's: drafting is a price, not a token's mathematics) whose
    queries score `pairs` (query, cached row) pairs: 2 x the matmul
    parameters a token meets (attention, the dense MLP, router, shared
    expert and `num_experts_per_tok` experts, the head for `head_tokens`
    of them: all if None), and the absorbed attention's two matmuls of
    `heads x (576 + 512)` a pair and layer."""
    H = cfg["hidden_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_layers"] - n_dense
    per_token = (cfg["num_layers"] * attention_params(cfg)
                 + n_dense * 3 * H * cfg["intermediate_size"]
                 + n_moe * (H * cfg["n_routed_experts"] + expert_params(cfg)
                            * (cfg["n_shared_experts"]
                               + cfg["num_experts_per_tok"])))
    head = (tokens if head_tokens is None else head_tokens) \
        * H * cfg["vocab_size"]
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return 2.0 * (tokens * per_token + head) + 2.0 * pairs \
        * cfg["num_attention_heads"] * (width + cfg["kv_lora_rank"]) \
        * cfg["num_layers"]


def window_flops(ticks: list, emitted: float, chunks: list,
                 cfg: dict) -> float:
    """Model FLOPs of the tokens a window EMITTED (each at the mean
    context of the running slots; a rejected position is time, not
    FLOPs) and prefilled (a chunk's rows, each scoring what precedes it;
    one head row a chunk at most, left out)."""
    active = sum(a["steps"] * a["active"] for a in ticks)
    context = sum(a["steps"] * a["kv_tokens"] for a in ticks) / active \
        if active else 0.0
    flops = token_flops(emitted, emitted * context, cfg)
    for a in chunks:
        q = a["q_tokens"]
        flops += token_flops(
            q, q * (a["kv_tokens"] - q) + q * (q + 1) // 2, cfg,
            head_tokens=0)
    return flops


# ------------------------------------------------------------ the readers

def _ticks(trace) -> list:
    return program_spans._attrs(trace, "serve:tick_dispatch", TICK_KEYS) \
        if trace is not None else []


def _chunks(trace) -> list:
    return program_spans._attrs(trace, "serve:chunk_dispatch",
                                ("q_tokens", "kv_tokens"))


def _emitted(trace) -> float:
    return sum(a["tokens"] for a in program_spans._attrs(
        trace, "serve:emit", ("tokens",)))


def mtp_accept_pct(trace, counters, args):
    """`accept_pct` of the traced window's device-side counts."""
    got = counters.get("mtp")
    return accept_pct(got[0], got[1]) if got else None


def tokens_per_forward_in_window(trace, counters, args):
    ticks = _ticks(trace)
    return tokens_per_forward(ticks, _emitted(trace)) if ticks else None


def scope_ms_per_forward(trace, counters, args):
    """Own device time of the scope `args["scope"]` inside the tick
    program, a verify forward (with its draft), in ms."""
    ns = sparse_mla._scope_ns(trace, args["scope"], MODULE)
    n = sum(a["steps"] for a in _ticks(trace)) if ns else 0
    return ns * 1e-6 / n if n else None


def mla_dense_roofline_pct(trace, counters, args):
    """Least time to read the latent rows the window's forwards need
    (`latent_row_bytes`, at the HBM bandwidth) over the summed own time
    of the custom calls matching `args["pattern"]` inside the tick's
    launches, in %."""
    path = program_spans._newest_pb()
    if trace is None or not trace.devices or path is None:
        return None
    ticks = _ticks(trace)
    peak = program_spans._peaks()
    if not ticks or peak is None:
        return None
    rx = re.compile(args["pattern"])
    took = sum(own for n, own in sparse_mla._own_times(trace, MODULE, path)
               if rx.search(n.split(" = ")[0])) * 1e-9
    if took <= 0:
        return None
    least = latent_row_bytes(
        ticks, program_spans._config(args["config"])) / peak[1]
    return 100.0 * least / took


def _hits_per_forward(counters):
    """Experts given at least a row, summed over the MoE blocks (the
    module's too), a verify forward of the traced window."""
    rows, n = counters.get("moe_rows"), counters.get("decode_steps")
    if not rows or not n:
        return None
    return sum(sum(layer[0][1]) for layer in rows) / n


def moe_experts_roofline_pct(trace, counters, args):
    """Least time to read the weights of the experts that were given a
    row (the traced window's count, a forward) at the HBM bandwidth, over
    the own time a forward of the `moe_experts` scope in the tick."""
    hits = _hits_per_forward(counters)
    per = scope_ms_per_forward(trace, counters, {"scope": "moe_experts"})
    peak = program_spans._peaks()
    if not hits or not per or peak is None:
        return None
    cfg = program_spans._config(args["config"])
    least = hits * expert_params(cfg) * ITEM / peak[1]
    return 100.0 * least / (per * 1e-3)


def weights_read_roofline_pct(trace, counters, args):
    """Least time to read every weight a verify forward and its draft
    must read (`forward_weight_bytes`, the experts by the device-side
    count) at the HBM bandwidth, over the launch's device time a forward,
    in %: the cell's real ceiling."""
    hits = _hits_per_forward(counters)
    per = program_spans.device_ms_per_step(
        trace, {}, {"module": MODULE, "span": "serve:tick_dispatch"}) \
        if trace is not None and trace.devices else None
    peak = program_spans._peaks()
    if not hits or not per or peak is None:
        return None
    least = forward_weight_bytes(
        hits, program_spans._config(args["config"])) / peak[1]
    return 100.0 * least / (per * 1e-3)


def serve_mfu_pct(trace, counters, args):
    """`window_flops` of the traced window over the chip's bf16 peak
    times the window, in %: the share of the whole step."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    ticks, chunks = _ticks(trace), _chunks(trace)
    peak = program_spans._peaks()
    if not (ticks or chunks) or peak is None:
        return None
    return 100.0 * window_flops(
        ticks, _emitted(trace), chunks,
        program_spans._config(args["config"])) / (peak[0] * trace.window_s)
