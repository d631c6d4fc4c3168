"""swa.py — what the self-drafted tick of a model with window and full
attention layers (`exaone_moe`: K-EXAONE's block, a ring of the last
`sliding_window` positions a slot in each window layer, paged kv-head pools
in the full layers and the MTP module) must read and compute, counted from
the program's spans and counters and the configuration, whatever
implements it; and the readers of its scopes inside `jit_serving_mtp_tick`.

A launch runs, for every running slot, ONE verify forward of the model
over two positions (the last token and the draft) and the module's
forward over the same two: `serve:tick_dispatch` says `steps` (verify
forwards: 1), `active`, `kv_tokens` (the context positions of the running
slots); `serve:chunk_dispatch` says `q_tokens` and `kv_tokens` of a prompt
chunk; `serve:emit` says `tokens`, what a tick handed over.  The expert
layers' row counts come to the host with each tick's tokens (`counters`:
`moe_rows`, `decode_steps`).

A window layer of a verify forward must read its slot's ring (K and V of
`sliding_window` positions) and write the forward's two positions into
it; a full layer (and the module) must read the K and V of every live
position of its slot.  A position's K and V in one layer are `2 x
num_key_value_heads x head_dim` bf16: 4 KiB at the published widths.

The byte and FLOP functions take plain lists and dicts so that a test can
check them by hand.  Every reader takes `(trace, counters, args)` and
returns a number, or None where there is nothing to read (no device
plane, a program without these scopes or spans — the parent of the PR
that added them — or a rehearsal).  No share of a peak is computed from
`max_batch`: only from what the spans and the device-side counts say ran.
"""

from __future__ import annotations

import re

from benchmark.reducers import mtp, program_spans, sparse_mla

ITEM = 2          # bytes of a bfloat16
ROUTER_ITEM = 4   # the router's weight is float32
MODULE = mtp.MODULE
POSITIONS = mtp.POSITIONS
SLIDING = "sliding_attention"


# ------------------------------------------------ counts, bytes and FLOPs

def windowed_layers(cfg: dict) -> int:
    """Window layers of the stage (the module attends the whole context)."""
    return sum(t == SLIDING for t in cfg["layer_types"][:cfg["num_layers"]])


def full_layers(cfg: dict) -> int:
    """Layers that attend the whole context: the stage's full layers and
    the module."""
    return cfg["num_layers"] - windowed_layers(cfg) \
        + cfg["num_nextn_predict_layers"]


def kv_position_bytes(cfg: dict) -> int:
    """Bytes of one position's K and V in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEM


def ring_moved_bytes(ticks: list, cfg: dict) -> float:
    """Bytes the window layers of these ticks' verify forwards must move:
    every running slot's ring read once, and the forward's two positions
    written, a window layer and forward."""
    rows = sum(a["steps"] * a["active"] for a in ticks)
    return float(rows) * windowed_layers(cfg) * kv_position_bytes(cfg) \
        * (cfg["sliding_window"] + POSITIONS)


def full_kv_bytes(ticks: list, cfg: dict) -> float:
    """Bytes of K and V the full layers and the module must read in these
    ticks' verify forwards: every live position of every running slot and
    the forward's own two, once a layer and forward."""
    pos = sum(a["steps"] * (a["kv_tokens"] + POSITIONS * a["active"])
              for a in ticks)
    return float(pos) * full_layers(cfg) * kv_position_bytes(cfg)


def attention_params(cfg: dict) -> int:
    H, nh, nkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * H * nh * hd + 2 * H * nkv * hd


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_layers(cfg: dict) -> int:
    """Expert layers a forward and its draft run: the stage's and the
    module's."""
    return sum(t == "sparse" for t in
               cfg["mlp_layer_types"][:cfg["num_layers"]]) \
        + cfg["num_nextn_predict_layers"]


def dense_weight_bytes(cfg: dict) -> float:
    """Bytes of the weights a verify forward and its draft read whatever
    the routing: every layer's attention projections, the dense layers'
    MLP, every expert layer's router (float32) and shared expert, the
    module's projection, and the head TWICE (the verify's logits choose
    the token the draft is made of).  Norm vectors and the embedding rows
    looked up are left out."""
    H = cfg["hidden_size"]
    L = cfg["num_layers"] + cfg["num_nextn_predict_layers"]
    n_dense = cfg["num_layers"] + cfg["num_nextn_predict_layers"] \
        - moe_layers(cfg)
    return float(
        L * attention_params(cfg) * ITEM
        + n_dense * 3 * H * cfg["intermediate_size"] * ITEM
        + moe_layers(cfg) * (H * cfg["router_width"] * ROUTER_ITEM
                             + expert_params(cfg) * cfg["num_shared_experts"]
                             * ITEM)
        + cfg["num_nextn_predict_layers"] * 2 * H * H * ITEM
        + 2 * H * cfg["vocab_size"] * ITEM)


def forward_weight_bytes(hits: float, cfg: dict) -> float:
    """... and with the `hits` experts (summed over the expert layers)
    that were given at least one row."""
    return dense_weight_bytes(cfg) + float(hits) * expert_params(cfg) * ITEM


def window_pairs(q: int, kv: int, window: int) -> int:
    """(query, key) pairs a window layer scores for `q` queries that end a
    context of `kv` positions: the query at position `t` sees `min(t + 1,
    window)` keys."""
    def upto(n):                 # sum of min(u, window) for u = 1..n
        w = min(n, window)
        return w * (w + 1) // 2 + max(0, n - window) * window
    return upto(kv) - upto(kv - q)


def token_flops(tokens: float, full_pairs: float, win_pairs: float,
                cfg: dict, head_tokens: float = None) -> float:
    """Model FLOPs this share does for `tokens` tokens through the MODEL's
    layers (not the module's: drafting is a price, not a token's
    mathematics) whose queries score `full_pairs` (query, key) pairs in
    each full layer and `win_pairs` in each window layer: 2 x the matmul
    parameters a token meets (attention, the dense MLP, router, shared
    expert, and of its `num_experts_per_tok` experts the share held here,
    `num_experts / router_width` of them; the head for `head_tokens`
    of them: all if None), and the attention's two matmuls of `heads x
    head_dim` a pair."""
    H, L = cfg["hidden_size"], cfg["num_layers"]
    n_moe = moe_layers(cfg) - cfg["num_nextn_predict_layers"]
    held = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_width"]
    per_token = (L * attention_params(cfg)
                 + (L - n_moe) * 3 * H * cfg["intermediate_size"]
                 + n_moe * (H * cfg["router_width"] + expert_params(cfg)
                            * (cfg["num_shared_experts"] + held)))
    head = (tokens if head_tokens is None else head_tokens) \
        * H * cfg["vocab_size"]
    nw = windowed_layers(cfg)
    pairs = full_pairs * (L - nw) + win_pairs * nw
    return 2.0 * (tokens * per_token + head) \
        + 4.0 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]


def window_flops(ticks: list, emitted: float, chunks: list,
                 cfg: dict) -> float:
    """Model FLOPs of the tokens a window EMITTED (each at the mean
    context of the running slots; a rejected position is time, not FLOPs)
    and prefilled (a chunk's rows, each scoring what precedes it; one head
    row a chunk at most, left out)."""
    W = cfg["sliding_window"]
    active = sum(a["steps"] * a["active"] for a in ticks)
    context = sum(a["steps"] * a["kv_tokens"] for a in ticks) / active \
        if active else 0.0
    flops = token_flops(emitted, emitted * context,
                        emitted * min(context, W), cfg)
    for a in chunks:
        q, kv = a["q_tokens"], a["kv_tokens"]
        flops += token_flops(q, q * (kv - q) + q * (q + 1) // 2,
                             window_pairs(q, kv, W), cfg, head_tokens=0)
    return flops


# ------------------------------------------------------------ the readers

def _ticks(trace) -> list:
    return mtp._ticks(trace)


def _kernel_s(trace, pattern: str):
    """Own seconds of the custom calls matching `pattern` inside the
    tick's launches; None where the trace has no device plane."""
    path = program_spans._newest_pb()
    if trace is None or not trace.devices or path is None:
        return None
    rx = re.compile(pattern)
    return sum(own for n, own in sparse_mla._own_times(trace, MODULE, path)
               if rx.search(n.split(" = ")[0])) * 1e-9


def swa_roofline_pct(trace, counters, args):
    """Least time to read the window layers' rings and write the verify
    forwards' positions into them (`ring_moved_bytes`, at the HBM
    bandwidth) over the own time of the scope `swa_attend` inside the
    tick's launches (the window attention and the ring's write), in %."""
    ticks = _ticks(trace)
    ns = sparse_mla._scope_ns(trace, "swa_attend", MODULE) if ticks \
        else None
    peak = program_spans._peaks()
    if not ns or peak is None:
        return None
    least = ring_moved_bytes(ticks, program_spans._config(args["config"])) \
        / peak[1]
    return 100.0 * least / (ns * 1e-9)


def full_decode_roofline_pct(trace, counters, args):
    """Least time to read the live K and V of the full layers and the
    module (`full_kv_bytes`, at the HBM bandwidth) over the own time of
    the custom calls matching `args["pattern"]` inside the tick's
    launches, in %: the kernel's grid also steps over every dead column
    of every slot's block table."""
    ticks = _ticks(trace)
    took = _kernel_s(trace, args["pattern"]) if ticks else None
    peak = program_spans._peaks()
    if not took or peak is None:
        return None
    least = full_kv_bytes(ticks, program_spans._config(args["config"])) \
        / peak[1]
    return 100.0 * least / took


def weights_read_roofline_pct(trace, counters, args):
    """Least time to read every weight a verify forward and its draft
    must read (`forward_weight_bytes`, the experts by the device-side
    count) and the K and V its attention must move (`ring_moved_bytes`,
    `full_kv_bytes`), at the HBM bandwidth, over the launch's device time
    a forward, in %: the cell's real ceiling."""
    hits = mtp._hits_per_forward(counters)
    ticks = _ticks(trace)
    per = program_spans.device_ms_per_step(
        trace, {}, {"module": MODULE, "span": "serve:tick_dispatch"}) \
        if trace is not None and trace.devices else None
    peak = program_spans._peaks()
    steps = sum(a["steps"] for a in ticks)
    if not hits or not per or not steps or peak is None:
        return None
    cfg = program_spans._config(args["config"])
    least = (forward_weight_bytes(hits, cfg) + (
        ring_moved_bytes(ticks, cfg) + full_kv_bytes(ticks, cfg)) / steps) \
        / peak[1]
    return 100.0 * least / (per * 1e-3)


def serve_mfu_pct(trace, counters, args):
    """`window_flops` of the traced window over the chip's bf16 peak
    times the window, in %: the share of the whole step."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    ticks, chunks = _ticks(trace), mtp._chunks(trace)
    peak = program_spans._peaks()
    if not (ticks or chunks) or peak is None:
        return None
    return 100.0 * window_flops(
        ticks, mtp._emitted(trace), chunks,
        program_spans._config(args["config"])) / (peak[0] * trace.window_s)
