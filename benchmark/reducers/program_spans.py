"""program_spans.py — readers of what the program names itself: its spans
(`paddle_tpu.observability.span`: `serve:*` around the serve loop's
phases, `to_static:*` around the capture stages), its programs (the
`XLA Modules` line reads `jit_serving_tick`, `jit_train_step`), its
kernels (`flash_fwd`, `paged_decode`, ... in the custom call's
instruction name) and its scopes (`forward`, `backward`,
`optimizer_step` in an op's `tf_op`).

`xplane.Trace` keeps event names only.  Where a metric needs a span's
attrs or an op's scope, the `.xplane.pb` itself is read: it still lies
under `<checkout>/.bench_trace/<cell>/plugins/profile/*/` while the
reducers run.  Spans that ended before the profiler started
(the capture stages) are read in-process from
`observability.span_totals()`.

Every reader takes `(trace, counters, args)` and returns a number, or
None where there is nothing to read: no device plane, a program without
these spans or names (the parent of the PR that added them), a rehearsal.
"""

from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re

from benchmark import peaks
from benchmark.reducers import xplane

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


# ------------------------------------------------- the trace's own file

def _newest_pb():
    found = glob.glob(os.path.join(ROOT, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _spans(path: str, mtime: float) -> list:
    """`[(start_ns, end_ns, name, attrs)]` of the program's own spans on
    `/host:CPU`, by start: `xplane.load` keeps names only."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                            dict(e.stats)) for e in ln.events
                           if e.name.startswith(("serve:", "to_static:")))
    return sorted(out, key=lambda x: x[0])


def spans_with_attrs(trace, in_window: bool = True) -> list:
    """The program's spans with their attrs, those that start inside the
    window or all the file holds; [] where the trace left no file."""
    path = _newest_pb()
    if trace is None or path is None:
        return []
    lo, hi = trace.window if in_window else (float("-inf"), float("inf"))
    return [x for x in _spans(path, os.path.getmtime(path))
            if lo <= x[0] < hi]


def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """`(field, value)` of one protobuf message: an int for a varint, a
    memoryview for a length-delimited or fixed field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        else:                           # 1: eight bytes, 5: four
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        yield key >> 3, v


def plane_metadata(path: str) -> dict:
    """`{plane name: [(event name, {stat name: text})]}`: every plane's
    event *metadata* with its string stats.  The profiler writes what is
    the same for every run of an op there (its `tf_op`, `hlo_category`),
    and `ProfileData` shows an event's own stats only, so the planes'
    two metadata maps are read from the file's bytes:
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5 (map entries: key = 1, value = 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, .str_value = 5.  The lines, which hold the
    events and nearly all the bytes, are skipped."""
    with open(path, "rb") as f:
        raw = memoryview(f.read())
    text = lambda v: bytes(v).decode("utf-8", "replace")   # noqa: E731
    out = {}
    for field, plane in _fields(raw):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = text(pv)
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                entry = dict(_fields(pv))
                stat_names[entry.get(1)] = text(
                    dict(_fields(entry[2])).get(2, b""))
        metadata = []
        for ent in events:
            op, stats = "", {}
            for mf, mv in _fields(dict(_fields(ent))[2]):
                if mf == 2:
                    op = text(mv)
                elif mf == 5:
                    stat = dict(_fields(mv))
                    if 5 in stat:
                        stats[stat_names.get(stat.get(1), "")] = text(stat[5])
            metadata.append((op, stats))
        out[name] = metadata
    return out


@functools.lru_cache(maxsize=2)
def _op_scopes(path: str, mtime: float) -> dict:
    """`{HLO text of an op: its tf_op}` of `/device:TPU:0`
    (`jit(train_step)/forward/jvp()/dot_general:`; "" for an op the
    compiler made); {} where the file has no such plane.  A device plane
    none of whose ops carries a `tf_op` is a format this reader does not
    know, and raises: the scopes' metrics must not fall silent."""
    ops = plane_metadata(path).get("/device:TPU:0")
    if ops is None:
        return {}
    out = {op: stats.get("tf_op", "") for op, stats in ops}
    if not any(out.values()):
        raise RuntimeError(
            f"{path}: none of the {len(out)} ops of /device:TPU:0 carries a "
            f"tf_op in the plane's event metadata; the profiler's format "
            f"has changed and program_spans.plane_metadata must follow it")
    return out


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _peaks():
    """(FLOP/s, bytes/s) of the chip jax runs on; None on a device the
    table does not know (a rehearsal's CPU)."""
    import jax
    try:
        kind = jax.devices()[0].device_kind
        return peaks.peak_flops(kind), peaks.peak_bytes_per_s(kind)
    except ValueError:
        return None


# ------------------------------------------------------------- programs

def _launches(trace, module: str) -> list:
    """Device durations (ns) of the launches of `module` on device 0
    that lie whole inside the window (a launch the window cuts would
    read short)."""
    lo, hi = trace.window
    return [e - s for s, e, n in trace.devices[min(trace.devices)]["modules"]
            if n.split("(")[0] == module and s > lo and e < hi]


def module_device_ms(trace, counters, args):
    """Mean device duration of a launch of the program `args["module"]`
    in the window, in ms."""
    if trace is None or not trace.devices:
        return None
    took = _launches(trace, args["module"])
    return sum(took) * 1e-6 / len(took) if took else None


def device_ms_per_step(trace, counters, args):
    """Device time of the program `args["module"]` a decode step, in ms:
    the summed durations of its launches that lie whole inside the window
    over the summed `steps` of the `args["span"]` spans that launched
    them.  A launch belongs to the last such span begun before it: the
    engine has one tick in flight, or two chained in order.  The mean of
    a *launch* moves with the mix of 1-step and k-step ticks; this does
    not."""
    if trace is None or not trace.devices:
        return None
    begun = [(s, a["steps"]) for s, _, n, a
             in spans_with_attrs(trace, in_window=False)
             if n == args["span"] and "steps" in a]
    lo, hi = trace.window
    took = steps = 0
    for s, e, n in trace.devices[min(trace.devices)]["modules"]:
        if n.split("(")[0] != args["module"] or not (s > lo and e < hi):
            continue
        i = bisect.bisect_right(begun, (s, float("inf"))) - 1
        if i >= 0:
            took, steps = took + e - s, steps + begun[i][1]
    return took * 1e-6 / steps if steps else None


# ---------------------------------------------------------- serve phases

def _overlap_ns(a: list, b: list) -> float:
    """Summed overlap of two lists of disjoint sorted intervals."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def idle_gaps_of(trace) -> list:
    """The intervals of the window in which no op ran on device 0."""
    busy = xplane.union(trace.devices[min(trace.devices)]["ops"])
    edges = [trace.window[0]] + [t for se in busy for t in se] \
        + [trace.window[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_under_spans_ms(trace, counters, args):
    """Device-idle time of the window that lies under a host span named
    in `args["spans"]`, over the count of `args["per"]` spans in the
    window, in ms: what the chip waits for the host a tick."""
    if trace is None or not trace.devices:
        return None
    per = sum(1 for _, _, n in trace.host if n == args["per"])
    if not per:
        return None
    under = xplane.union(x for x in trace.host if x[2] in args["spans"])
    return _overlap_ns(idle_gaps_of(trace), under) * 1e-6 / per


# -------------------------------------------------------------- kernels

def kernel_s(trace, pattern: str) -> float:
    """Summed device seconds on device 0 of the ops whose instruction
    name (what stands before ` = `) matches `pattern`."""
    rx = re.compile(pattern)
    return sum(e - s for s, e, n in trace.devices[min(trace.devices)]["ops"]
               if rx.search(n.split(" = ")[0])) * 1e-9


def flash_roofline_pct(trace, counters, args):
    """`args["share"]` of the causal attention FLOPs the traced steps
    need (the forward's 2 or the backward's 4 of 6 matmuls) over the
    chip's peak, over the time of the custom calls whose name matches
    `args["pattern"]`, in %.  FLOP-bound; recomputation not counted."""
    steps = counters.get("traced_steps")
    flops = counters.get("attn_flops_per_step")
    peak = counters.get("peak_flops")
    if trace is None or not trace.devices or not (steps and flops and peak):
        return None
    took = kernel_s(trace, args["pattern"])
    if took <= 0:
        return None
    return 100.0 * (args["share"] * steps * flops / peak) / took


def decode_kv_bytes(ticks: list, cfg: dict, itemsize: int = 2) -> float:
    """Bytes of K and V the decode calls of these ticks must read: a tick
    of `steps` steps over `active` slots holding `kv_tokens` tokens reads,
    at step j, every slot's context with the j + 1 tokens written since;
    a token is `2 * num_layers * hidden_size` numbers.  Queries and
    outputs are a few kilobytes a step and are left out."""
    tokens = sum(a["steps"] * a["kv_tokens"]
                 + a["active"] * a["steps"] * (a["steps"] + 1) // 2
                 for a in ticks)
    return float(tokens) * 2 * cfg["num_layers"] * cfg["hidden_size"] \
        * itemsize


def chunk_work(chunks: list, cfg: dict, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) the chunk-prefill calls need: a chunk of `q_tokens`
    queries ending a context of `kv_tokens` scores each query against
    what precedes it and itself (two matmuls of `hidden_size` a layer),
    and reads that context's K and V once, its queries, and writes its
    outputs."""
    pairs = sum(a["q_tokens"] * (a["kv_tokens"] - a["q_tokens"])
                + a["q_tokens"] * (a["q_tokens"] + 1) // 2 for a in chunks)
    numbers = sum(2 * a["kv_tokens"] + 2 * a["q_tokens"] for a in chunks)
    per_layer = cfg["num_layers"] * cfg["hidden_size"]
    return 4.0 * pairs * per_layer, float(numbers) * per_layer * itemsize


def _attrs(trace, name: str, keys: tuple) -> list:
    return [a for _, _, n, a in spans_with_attrs(trace)
            if n == name and all(k in a for k in keys)]


def paged_decode_roofline_pct(trace, counters, args):
    """Least time to read the K and V that the window's decode calls need
    (bytes-bound, at the chip's HBM bandwidth) over the summed time of
    the `paged_decode` custom calls, in %."""
    if trace is None or not trace.devices:
        return None
    ticks = _attrs(trace, "serve:tick_dispatch",
                   ("steps", "active", "kv_tokens"))
    took = kernel_s(trace, args["pattern"])
    peak = _peaks()
    if not ticks or took <= 0 or peak is None:
        return None
    least = decode_kv_bytes(ticks, _config(args["config"])) / peak[1]
    return 100.0 * least / took


def paged_chunk_roofline_pct(trace, counters, args):
    """Least time for the window's chunk-prefill calls — the larger of
    their FLOPs over the peak and their bytes over the bandwidth, summed
    over the window — over the summed time of the `paged_chunk_prefill`
    custom calls, in %."""
    if trace is None or not trace.devices:
        return None
    chunks = _attrs(trace, "serve:chunk_dispatch", ("q_tokens", "kv_tokens"))
    took = kernel_s(trace, args["pattern"])
    peak = _peaks()
    if not chunks or took <= 0 or peak is None:
        return None
    flops, nbytes = chunk_work(chunks, _config(args["config"]))
    return 100.0 * max(flops / peak[0], nbytes / peak[1]) / took


# --------------------------------------------------------------- scopes

def scope_of(tf_op: str) -> str:
    """The first scope of an op below its program: `forward` of
    `jit(train_step)/forward/jvp()/dot_general:`; "" where there is
    none (`jit(train_step)/reduce_sum:`)."""
    parts = [p for p in tf_op.split("/")
             if p and not p.startswith(("jit(", "pjit("))]
    return parts[0] if len(parts) > 1 else ""


def own_ns_by_scope(ops: list, tf_ops: dict) -> dict:
    """`{scope: own ns}` of device ops `(start, end, name)`.  An op's own
    time is its duration less its children's (`xplane.self_times`), and
    it counts to the scope its own `tf_op` names.  An op the compiler
    made carries none — the `copy-done` and `async-done` that wait for a
    prefetch between memory spaces, a tenth of a train step — and counts
    to "", with the ops the program put under no scope."""
    acc, stack = {}, []                    # stack of [end, scope, own]
    for s, e, n in sorted(ops, key=lambda x: (x[0], x[0] - x[1])):
        while stack and s >= stack[-1][0]:
            _, scope, own = stack.pop()
            acc[scope] = acc.get(scope, 0) + own
        if stack and e <= stack[-1][0]:
            stack[-1][2] -= e - s
        stack.append([e, scope_of(tf_ops.get(n, "")), e - s])
    for _, scope, own in stack:
        acc[scope] = acc.get(scope, 0) + own
    return acc


def scope_device_ms(trace, counters, args):
    """Own device time a step of the ops on device 0 whose `tf_op` names
    the scope `args["scope"]`, in ms; "" reads the ops under no scope
    (see `own_ns_by_scope`)."""
    steps = counters.get("traced_steps")
    path = _newest_pb()
    if trace is None or not trace.devices or not steps or path is None:
        return None
    took = own_ns_by_scope(
        trace.devices[min(trace.devices)]["ops"],
        _op_scopes(path, os.path.getmtime(path))).get(args["scope"])
    return took * 1e-6 / steps if took else None


# ------------------------------------------------------- capture stages

def span_total_s(trace, counters, args):
    """The in-memory total of the span `args["span"]`, in seconds: the
    capture stages end before the profiler starts."""
    try:
        from paddle_tpu.observability import span_totals
    except ImportError:
        return None
    got = span_totals(args["span"]).get(args["span"])
    return got["total_s"] if got else None
