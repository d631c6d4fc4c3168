"""blocks.py — what the tick of a block-diffusion model (`sdar_moe`) must
read and compute, counted from the program's spans and counters and the
configuration, whatever implements it; and the readers of the model's
scopes inside the block tick program.

A launch of `jit_serving_block_tick` runs, for every running slot, the
`denoising_steps` denoising forwards of one block of `block_len`
positions and the forward that commits it: `serve:tick_dispatch` says
`steps` (the forwards of the launch), `active`, `kv_tokens` (the
committed tokens of the running slots) and `block_len`; `serve:emit`
says `tokens`, what the tick handed over.

The byte and FLOP functions take plain lists and dicts so that a test can
check them by hand.  Every reader takes `(trace, counters, args)` and
returns a number, or None where there is nothing to read (no device
plane, a program without these scopes or spans — the parent of the PR
that added them — or a rehearsal).
"""

from __future__ import annotations

import re

from benchmark.reducers import program_spans, sparse_mla

ITEM = 2          # bytes of a bfloat16
MODULE = "jit_serving_block_tick"
TICK_KEYS = ("steps", "active", "kv_tokens", "block_len")


# ------------------------------------------------ bytes and FLOPs, by hand

def forwards_per_token(ticks: list, tokens: float):
    """Sequence-forwards (a slot's block through one forward) over the
    tokens handed over: `steps / block_len` = 1.25 for whole blocks of 4
    in 5 forwards; more where a first block holds prompt tokens or a last
    one is cut by the request's budget."""
    rows = sum(a["steps"] * a["active"] for a in ticks)
    return rows / tokens if tokens else None


def kv_read_bytes(ticks: list, cfg: dict) -> float:
    """Bytes of K and V the attention of these ticks must read: every
    forward of a tick reads, a layer, each running slot's committed
    context and the block's own rows, `num_key_value_heads * head_dim`
    bf16 a token for K and as much for V.  Queries and outputs (32 heads
    x 4 positions a slot) are left out."""
    tokens = sum(a["steps"] * (a["kv_tokens"] + a["active"] * a["block_len"])
                 for a in ticks)
    return float(tokens) * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * ITEM * cfg["num_layers"]


def expert_weight_bytes(hits: float, cfg: dict) -> float:
    """Bytes of expert weights read when `hits` (layer, forward, expert)
    triples had an expert given at least one row: three matrices of
    `hidden_size x moe_intermediate_size` bf16 each."""
    return float(hits) * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * ITEM


def dense_weight_bytes(cfg: dict, head: bool = True) -> float:
    """Bytes of the weights every forward reads whatever the routing: a
    layer's four attention projections and its router, and (`head`) the
    output head over the whole vocabulary.  Norm vectors and the rows of
    the embedding a forward looks up are left out (kilobytes)."""
    H, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = 2 * H * nh * hd + 2 * H * nkv * hd + H * cfg["num_experts"]
    return float(cfg["num_layers"] * layer
                 + (H * cfg["vocab_size"] if head else 0)) * ITEM


def launch_weight_bytes(n_forwards: int, hits_per_layer_forward: float,
                        cfg: dict) -> float:
    """Bytes of weights one launch of `n_forwards` forwards must read:
    every forward the attention and router weights and the experts that
    were given a row; every forward but the commit the output head, and
    the commit not its last layer's experts (it keeps K and V, which are
    computed before them)."""
    layer_forwards = n_forwards * cfg["num_layers"] - 1
    return n_forwards * dense_weight_bytes(cfg, head=False) \
        + (n_forwards - 1) * cfg["hidden_size"] * cfg["vocab_size"] * ITEM \
        + expert_weight_bytes(hits_per_layer_forward * layer_forwards, cfg)


def forward_flops(rows: float, pairs: float, cfg: dict,
                  head_rows: float = None) -> float:
    """Model FLOPs of `rows` token rows whose queries score `pairs`
    (query, key) pairs: 2 x the matmul parameters a row meets (the
    attention projections, the router, `num_experts_per_tok` experts, the
    head for `head_rows` of them: all if None), and the attention's two
    matmuls of `num_attention_heads * head_dim` a pair and layer."""
    H, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_row = cfg["num_layers"] * (
        2 * H * nh * hd + 2 * H * nkv * hd + H * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * 3 * H * cfg["moe_intermediate_size"])
    head = (rows if head_rows is None else head_rows) * H * cfg["vocab_size"]
    return 2.0 * (rows * per_row + head) \
        + 4.0 * pairs * nh * hd * cfg["num_layers"]


def window_flops(ticks: list, chunks: list, cfg: dict) -> float:
    """Model FLOPs of what the window ran: every forward of every tick
    over `active * block_len` rows, each row scoring its slot's committed
    context and the block (the commit forward needs no head); every
    prefill chunk's rows, each scoring what precedes it and its own block
    (no head: a prompt is prefilled for its K and V)."""
    flops = 0.0
    for a in ticks:
        rows = a["active"] * a["block_len"]
        pairs = a["block_len"] * (a["kv_tokens"] + rows)
        flops += forward_flops(a["steps"] * rows, a["steps"] * pairs, cfg,
                               head_rows=(a["steps"] - 1) * rows)
    for a in chunks:
        q = a["q_tokens"]
        pairs = q * (a["kv_tokens"] - q) + q * (q + 1) // 2
        flops += forward_flops(q, pairs, cfg, head_rows=0)
    return flops


# ------------------------------------------------------------ the readers

def _ticks(trace) -> list:
    return program_spans._attrs(trace, "serve:tick_dispatch", TICK_KEYS)


def _chunks(trace) -> list:
    return program_spans._attrs(trace, "serve:chunk_dispatch",
                                ("q_tokens", "kv_tokens"))


def _forwards(trace) -> int:
    return sum(a["steps"] for a in _ticks(trace)) if trace is not None else 0


def forwards_per_token_in_window(trace, counters, args):
    """`forwards_per_token` over the window's tick and emit spans."""
    if trace is None:
        return None
    ticks = _ticks(trace)
    tokens = sum(a["tokens"] for a in program_spans._attrs(
        trace, "serve:emit", ("tokens",)))
    return forwards_per_token(ticks, tokens) if ticks else None


def scope_ms_per_forward(trace, counters, args):
    """Own device time of the scope `args["scope"]` inside the block tick
    program, a forward of its launches (denoising and commit alike), in
    ms."""
    ns = sparse_mla._scope_ns(trace, args["scope"], MODULE)
    n = _forwards(trace) if ns else 0
    return ns * 1e-6 / n if n else None


def _forward_ms(trace):
    """Device time of the block tick program a forward, in ms."""
    return program_spans.device_ms_per_step(
        trace, {}, {"module": MODULE, "span": "serve:tick_dispatch"})


def _hits_per_layer_forward(counters):
    """Experts given at least a row, a layer and forward of the traced
    window's ticks, from the device-side counter."""
    rows, n = counters.get("moe_rows"), counters.get("decode_steps")
    if not rows or not n:
        return None
    return sum(sum(layer[0][1]) for layer in rows) / (n * len(rows))


def moe_experts_roofline_pct(trace, counters, args):
    """Least time to read the weights of the experts that were given a
    row (the traced window's count, a forward) at the HBM bandwidth, over
    the own time a forward of the `moe_experts` scope in the block tick."""
    hits = _hits_per_layer_forward(counters)
    per = scope_ms_per_forward(trace, counters, {"scope": "moe_experts"})
    peak = program_spans._peaks()
    if not hits or not per or peak is None:
        return None
    cfg = program_spans._config(args["config"])
    least = expert_weight_bytes(hits * cfg["num_layers"], cfg) / peak[1]
    return 100.0 * least / (per * 1e-3)


def paged_block_roofline_pct(trace, counters, args):
    """Least time to read the K and V that the window's block ticks need
    (bytes-bound, at the HBM bandwidth) over the summed time of the
    custom calls matching `args["pattern"]` inside the block tick's
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
    least = kv_read_bytes(
        ticks, program_spans._config(args["config"])) / peak[1]
    return 100.0 * least / took


def weights_read_roofline_pct(trace, counters, args):
    """Least time to read the weights a launch of the block tick must
    read (`launch_weight_bytes`, the experts by the device-side count) at
    the HBM bandwidth, over the launch's device time, in %: the cell's
    real ceiling."""
    hits = _hits_per_layer_forward(counters)
    per = _forward_ms(trace) if trace is not None and trace.devices else None
    peak = program_spans._peaks()
    ticks = _ticks(trace) if per else []
    if not hits or not per or peak is None or not ticks:
        return None
    n = max(a["steps"] for a in ticks)
    cfg = program_spans._config(args["config"])
    least = launch_weight_bytes(n, hits, cfg) / peak[1]
    return 100.0 * least / (per * 1e-3 * n)


def serve_mfu_pct(trace, counters, args):
    """Model FLOPs of the forwards and prefill chunks the traced window
    ran (`window_flops`, from the spans' attrs) over the chip's bf16 peak
    times the window, in %."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    ticks, chunks = _ticks(trace), _chunks(trace)
    peak = program_spans._peaks()
    if not (ticks or chunks) or peak is None:
        return None
    return 100.0 * window_flops(
        ticks, chunks, program_spans._config(args["config"])) \
        / (peak[0] * trace.window_s)
