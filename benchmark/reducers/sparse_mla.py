"""sparse_mla.py — what each new kernel of a latent-cache model with a
sparse index (`glm_moe_dsa`) must move and compute, counted from the
program's spans and counters and the configuration, whatever implements
the kernel; and the readers of the model's scopes inside the serving
programs.

The byte and FLOP functions take plain lists and dicts so that a test can
check them by hand.  Every reader takes `(trace, counters, args)` and
returns a number, or None where there is nothing to read (no device
plane, a program without these scopes or spans, a rehearsal).

A scope's time is the own device time of the ops whose `tf_op` path
holds the scope's name as a component (`jit(tick)/while/body/.../
dsa_index/dot_general`): the scopes sit inside the tick's scan, so
`program_spans.scope_of` (the FIRST component) cannot see them.
"""

from __future__ import annotations

import os

from benchmark.reducers import program_spans, xplane

ITEM = 2          # bytes of a bfloat16


# ------------------------------------------------ bytes and FLOPs, by hand

def tick_contexts(ticks: list) -> float:
    """Sum over the decode steps of these ticks of the cached tokens their
    sequences hold: a tick of `steps` over `active` slots holding
    `kv_tokens` reads, at step j, every context with the j + 1 tokens
    written since."""
    return float(sum(a["steps"] * a["kv_tokens"]
                     + a["active"] * a["steps"] * (a["steps"] + 1) // 2
                     for a in ticks))


def index_key_bytes(ticks: list, cfg: dict) -> float:
    """Bytes the indexer of the decode steps must read: every running
    context's index keys, `index_head_dim` bf16 a token a layer a step."""
    return tick_contexts(ticks) * cfg["index_head_dim"] * ITEM \
        * cfg["num_layers"]


def selected_row_bytes(ticks: list, cfg: dict) -> float:
    """Bytes of latent rows the sparse attention of the decode steps must
    read: `selected_tokens` (min(context, index_topk) a sequence, as the
    span counted them at dispatch) rows of `kv_lora_rank +
    qk_rope_head_dim` bf16 a layer a step.  The growth of a context
    inside a tick is left out: it is below one row in 2,048."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return float(sum(a["steps"] * a["selected_tokens"] for a in ticks)) \
        * width * ITEM * cfg["num_layers"]


def expert_weight_bytes(hits: float, cfg: dict) -> float:
    """Bytes of expert weights read when `hits` (layer, step, expert)
    triples had an expert given at least one row: three matrices of
    `hidden_size x moe_intermediate_size` bf16 each."""
    return float(hits) * 3 * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * ITEM


def step_flops(tokens: float, contexts: float, selected: float,
               cfg: dict) -> float:
    """Model FLOPs of `tokens` tokens whose queries see `contexts` cached
    tokens and attend `selected` of them, as this share runs them: 2 x
    the matmul parameters a token meets (attention, indexer, router,
    shared expert, `num_experts_per_tok * held / router_width` routed
    experts on average, the dense layers' MLP, the head over the
    vocabulary slice), the indexer's scores over the context, and the
    absorbed attention (scores and values) over the selection."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    dc, ql = cfg["kv_lora_rank"], cfg["q_lora_rank"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    attn = (H * ql + ql * nh * (dn + dr) + H * (dc + dr)
            + dc * nh * (dn + dv) + nh * dv * H)
    index = ql * hi * di + H * di + H * hi
    expert = 3 * H * cfg["moe_intermediate_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_layers"] - n_dense
    held = cfg["n_routed_experts"] / cfg["router_width"]
    per_token = (cfg["num_layers"] * (attn + index)
                 + n_dense * 3 * H * cfg["intermediate_size"]
                 + n_moe * (H * cfg["router_width"]
                            + expert * cfg["n_shared_experts"]
                            + expert * cfg["num_experts_per_tok"] * held)
                 + H * cfg["vocab_size"])
    return 2.0 * (tokens * per_token
                  + cfg["num_layers"] * (contexts * hi * di
                                         + selected * nh * (2 * dc + dr)))


# ------------------------------------------------------------ the readers

def _ticks(trace) -> list:
    return program_spans._attrs(
        trace, "serve:tick_dispatch",
        ("steps", "active", "kv_tokens", "selected_tokens"))


def _chunks(trace) -> list:
    return program_spans._attrs(
        trace, "serve:chunk_dispatch",
        ("q_tokens", "kv_tokens", "selected_tokens"))


_OWN = {}        # (trace file, its mtime, module) -> [(op name, own ns)]


def _own_times(trace, module: str, path: str) -> list:
    """`(name, own ns)` of the device ops that run inside launches of
    `module`, computed once a trace: seven metrics read it."""
    key = (path, os.path.getmtime(path), module)
    if key not in _OWN:
        dev = trace.devices[min(trace.devices)]
        inside = xplane.union(x for x in dev["modules"]
                              if x[2].split("(")[0] == module)
        ops, j = [], 0
        for op in sorted(dev["ops"]):              # by start: one sweep
            while j < len(inside) and inside[j][1] <= op[0]:
                j += 1
            if j < len(inside) and inside[j][0] < op[1]:
                ops.append(op)
        _OWN.clear()
        _OWN[key] = xplane.self_times(ops)
    return _OWN[key]


def _scope_ns(trace, scope: str, module: str):
    """Own device ns of the ops inside launches of `module` whose tf_op
    path holds `scope`; None where the trace names no such op."""
    path = program_spans._newest_pb()
    if trace is None or not trace.devices or path is None:
        return None
    tf_ops = program_spans._op_scopes(path, os.path.getmtime(path))
    took = [own for n, own in _own_times(trace, module, path)
            if scope in tf_ops.get(n, "").split("/")]
    return sum(took) if took else None


def scope_ms_per_decode_step(trace, counters, args):
    """Own device time of the scope `args["scope"]` inside the tick
    program, a decode step, in ms."""
    ns = _scope_ns(trace, args["scope"], "jit_serving_tick")
    steps = sum(a["steps"] for a in _ticks(trace)) if ns else 0
    return ns * 1e-6 / steps if steps else None


def _roofline(trace, scope: str, nbytes: float):
    ns = _scope_ns(trace, scope, "jit_serving_tick")
    peak = program_spans._peaks()
    if not ns or peak is None or nbytes <= 0:
        return None
    return 100.0 * (nbytes / peak[1]) / (ns * 1e-9)


def dsa_index_roofline_pct(trace, counters, args):
    """Least time to read the index keys the window's decode steps need
    over the own time of the `dsa_index` scope in the tick program."""
    ticks = _ticks(trace) if trace is not None else []
    if not ticks:
        return None
    return _roofline(trace, "dsa_index", index_key_bytes(
        ticks, program_spans._config(args["config"])))


def mla_sparse_roofline_pct(trace, counters, args):
    """Least time to read the selected latent rows of the window's decode
    steps over the own time of the `mla_attend` scope."""
    ticks = _ticks(trace) if trace is not None else []
    if not ticks:
        return None
    return _roofline(trace, "mla_attend", selected_row_bytes(
        ticks, program_spans._config(args["config"])))


def moe_experts_roofline_pct(trace, counters, args):
    """Least time to read the weights of the held experts that were given
    a row, a decode step (the traced window's count of such experts,
    from the device-side counter the job read as the trace began and
    ended), over the own time a step of the `moe_experts` scope in the
    tick program."""
    rows, steps = counters.get("moe_rows"), counters.get("decode_steps")
    per_step = scope_ms_per_decode_step(trace, counters,
                                        {"scope": "moe_experts"})
    peak = program_spans._peaks()
    if not rows or not steps or not per_step or peak is None:
        return None
    hits = sum(sum(layer[0][1]) for layer in rows) / steps
    least = expert_weight_bytes(
        hits, program_spans._config(args["config"])) / peak[1]
    return 100.0 * least / (per_step * 1e-3)


def moe_tokens_per_expert(trace, counters, args):
    """Rows a held expert was given a decode step and MoE layer in the
    traced window, from the device-side counter."""
    rows, steps = counters.get("moe_rows"), counters.get("decode_steps")
    if not rows or not steps:
        return None
    moe = [layer[0][0] for layer in rows if sum(layer[0][0])]
    if not moe:
        return None
    return sum(sum(r) / len(r) for r in moe) / len(moe) / steps


def serve_mfu_pct(trace, counters, args):
    """Model FLOPs of the tokens the traced window decoded and prefilled
    (`step_flops`, from the spans' attrs) over the chip's bf16 peak times
    the window."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    ticks, chunks = _ticks(trace), _chunks(trace)
    peak = program_spans._peaks()
    if not (ticks or chunks) or peak is None:
        return None
    cfg = program_spans._config(args["config"])
    tokens = sum(a["steps"] * a["active"] for a in ticks) \
        + sum(a["q_tokens"] for a in chunks)
    contexts = tick_contexts(ticks) + sum(
        a["q_tokens"] * (a["kv_tokens"] - a["q_tokens"])
        + a["q_tokens"] * (a["q_tokens"] + 1) // 2 for a in chunks)
    selected = sum(a["steps"] * a["selected_tokens"] for a in ticks) \
        + sum(a["selected_tokens"] for a in chunks)
    return 100.0 * step_flops(tokens, contexts, selected, cfg) \
        / (peak[0] * trace.window_s)
