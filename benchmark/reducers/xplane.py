"""xplane.py — from the profiler's `.xplane.pb` to numbers.

`load(path)` reads the trace with nothing but JAX
(`jax.profiler.ProfileData`) into a `Trace`: for each device plane the
events of its `XLA Ops` and `XLA Modules` lines, for the host the named
spans of every thread, all clipped to the traced window.  The window is
the span named `bench_window`, which `run.py` opens around the traced part
of a run; without it, the extent of the device events.

What the planes hold on a v5e today (PR 24): `/device:TPU:<n>` with lines
`XLA Modules` (one event a program launch), `XLA Ops` (one event an HLO
op; a `while` op *contains* the ops of its body, so durations are not
summed — busy time is the union of intervals and an op's own time is its
duration less its children's) and `Async XLA Ops`; `/host:CPU` with a line
a thread, where JAX's own spans (`PjitFunction(...)`,
`np.asarray(jax.Array)`) and the benchmark's `TraceAnnotation`s lie.

The metric functions at the bottom are the readers that
`benchmark/layer_metrics/*.json` name as `xplane:<function>`; each takes
`(trace, counters, args)` and returns a number, or None where the trace
holds nothing to read.
"""

from __future__ import annotations

import re

WINDOW = "bench_window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?\b")


def _opcode(name: str) -> str:
    """The opcode of an HLO line `%n = <type> opcode(operands)`.  A type
    holds brackets, braces and parentheses (layouts, tuples), so the type
    ends at the first blank outside all of them."""
    _, eq, rest = name.partition(" = ")
    if not eq:
        return ""
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return rest[i + 1:].split("(")[0].strip()
    return ""


class Trace:
    def __init__(self, window, devices, host):
        self.window = window        # (start_ns, end_ns)
        self.devices = devices      # {ordinal: {"ops": [...], "modules": [...]}}
        self.host = host            # [(start_ns, end_ns, name)]
        self._busy_s = None         # computed once, several readers ask

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_kind(name: str) -> str:
    """The class an op's time is summed under in the breakdown: its
    opcode, and for a custom call also its target where the name shows
    it (`custom-call:tpu_custom_call` is a Mosaic kernel)."""
    code = _opcode(name) or name.split(" = ")[0].lstrip("%")[:80]
    if code == "custom-call":
        m = re.search(r'custom_call_target="([^"]+)"', name)
        return f"custom-call:{m.group(1)}" if m else code
    return code


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    if path.endswith(".gz"):          # a recorded fixture, kept small
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            devices[int(m.group(1))] = {
                key: [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in ln.events)
    return from_events(devices, host)


def from_events(devices: dict, host: list) -> Trace:
    """Clip everything to the window (see the module's head)."""
    marks = [(s, e) for s, e, n in host if n == WINDOW]
    if marks:
        window = max(marks, key=lambda se: se[1] - se[0])
    else:
        every = [x for d in devices.values() for x in d["ops"]]
        if not every:
            return Trace((0.0, 0.0), devices, host)
        window = (min(s for s, _, _ in every), max(e for _, e, _ in every))

    def clip(evs):
        return sorted((max(s, window[0]), min(e, window[1]), n)
                      for s, e, n in evs
                      if e > window[0] and s < window[1])
    return Trace(window,
                 {k: {"ops": clip(d["ops"]), "modules": clip(d["modules"])}
                  for k, d in devices.items()},
                 [x for x in clip(host) if x[2] != WINDOW])


def union(intervals) -> list:
    """Merged, sorted, disjoint `(start, end)` from any intervals."""
    out = []
    for s, e in sorted((x[0], x[1]) for x in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _total(merged) -> float:
    return sum(e - s for s, e in merged)


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran on the device, averaged over devices."""
    if trace._busy_s is None:
        trace._busy_s = sum(
            _total(union(d["ops"])) for d in trace.devices.values()) \
            * 1e-9 / max(1, len(trace.devices))
    return trace._busy_s


def self_times(ops) -> list:
    """`(name, own_ns)` an op: its duration less that of the ops nested
    inside it (a `while` holds its body).  `ops` sorted by start."""
    out, stack = [], []           # stack of [end, name, own]
    for s, e, n in sorted(ops, key=lambda x: (x[0], -(x[1] - x[0]))):
        while stack and s >= stack[-1][0]:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack and e <= stack[-1][0]:        # nested, not just overlapping
            stack[-1][2] -= (e - s)
        stack.append([e, n, e - s])
    out.extend((n, own) for _, n, own in stack)
    return out


def top_ops(trace: Trace, limit: int = 10) -> list:
    """The device's time by class of op on the busiest device, as
    `[[class, seconds], ...]`, own time only, most first."""
    if not trace.devices:
        return []
    ops = max(trace.devices.values(),
              key=lambda d: _total(union(d["ops"])))["ops"]
    acc = {}
    for name, own in self_times(ops):
        k = op_kind(name)
        acc[k] = acc.get(k, 0.0) + own
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[k, v * 1e-9] for k, v in top]


def idle_gaps(trace: Trace, limit: int = 10, longest: int = 500) -> list:
    """The idle time of device 0 by what the host was doing meanwhile:
    each gap between ops goes to the named host span that overlaps it
    most (of equals, the shortest: the innermost names it best), and the
    gaps are summed a name: `[[name, seconds], ...]`.  Only the `longest`
    gaps are looked up; the rest are summed as `(short gaps)`."""
    import bisect
    if not trace.devices:
        return []
    dev = trace.devices[min(trace.devices)]
    edges = [(trace.window[0], trace.window[0])] + \
        [tuple(x) for x in union(dev["ops"])] + \
        [(trace.window[1], trace.window[1])]
    gaps = sorted(((a[1], b[0]) for a, b in zip(edges, edges[1:])
                   if b[0] > a[1]), key=lambda g: g[0] - g[1])
    spans = sorted(trace.host)
    starts = [x[0] for x in spans]
    reach, far = [], float("-inf")       # latest end among spans[0..j]
    for x in spans:
        far = max(far, x[1])
        reach.append(far)
    acc = {}
    if gaps[longest:]:
        acc["(short gaps)"] = sum(e - s for s, e in gaps[longest:])
    for gs, ge in gaps[:longest]:
        best = (0.0, 0.0, "(no host span)")   # (overlap, -length, name)
        j = bisect.bisect_left(starts, ge) - 1
        while j >= 0 and reach[j] > gs:
            s, e, n = spans[j]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                best = max(best, (round(ov / (ge - gs), 3), s - e, n))
            j -= 1
        acc[best[2]] = acc.get(best[2], 0.0) + (ge - gs)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
    return [[k[:120], v * 1e-9] for k, v in top]


def module_times(trace: Trace) -> dict:
    """Device seconds a program on device 0, by the program's name with
    its fingerprint cut off: `{"jit_tick": 1.93, ...}`."""
    if not trace.devices:
        return {}
    acc = {}
    for s, e, n in trace.devices[min(trace.devices)]["modules"]:
        k = n.split("(")[0]
        acc[k] = acc.get(k, 0.0) + (e - s) * 1e-9
    return acc


def _matching_s(trace: Trace, pattern: str) -> float:
    """Summed device seconds of ops whose name matches, averaged over
    devices.  Meant for leaf ops (custom calls), which hold no others."""
    rx = re.compile(pattern)
    if not trace.devices:
        return 0.0
    return sum((e - s) for d in trace.devices.values()
               for s, e, n in d["ops"] if rx.search(n)) * 1e-9 \
        / len(trace.devices)


# ------------------------------------------------------------- readers

def device_idle_pct(trace, counters, args):
    """1 - union of device-op intervals over the traced window, in %."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def step_device_ms(trace, counters, args):
    """Device busy time over the steps the job counted in the window."""
    steps = counters.get("traced_steps")
    if trace is None or not trace.devices or not steps:
        return None
    return busy_s(trace) * 1e3 / steps


def matching_time_pct(trace, counters, args):
    """Share of device busy time in ops whose name matches
    `args["pattern"]`, in %."""
    if trace is None or not trace.devices:
        return None
    busy = busy_s(trace)
    if busy <= 0:
        return None
    return 100.0 * _matching_s(trace, args["pattern"]) / busy


def attn_kernel_roofline_pct(trace, counters, args):
    """Least time the chip could take for the causal attention the traced
    steps need (FLOP-bound: `attn_flops_per_step` over the peak, both from
    `benchmark/peaks.py` through the job's counters) over the summed
    duration of the custom calls that compute it, in %."""
    steps = counters.get("traced_steps")
    flops = counters.get("attn_flops_per_step")
    peak = counters.get("peak_flops")
    if trace is None or not trace.devices or not (steps and flops and peak):
        return None
    took = _matching_s(trace, args["pattern"])
    if took <= 0:
        return None
    return 100.0 * (steps * flops / peak) / took


def collective_exposed_pct(trace, counters, args):
    """On device 0: time inside collective ops during which no other op
    runs, over the window, in %."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    ops = trace.devices[min(trace.devices)]["ops"]
    coll = union(x for x in ops if _COLLECTIVE.search(x[2]))
    if not coll:
        return None
    # a `while`/`conditional` spans its body and is no work of its own
    other = union(x for x in ops if not _COLLECTIVE.search(x[2])
                  and op_kind(x[2]) not in ("while", "conditional", "call"))
    hidden, j = 0.0, 0
    for s, e in coll:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            hidden += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return 100.0 * (_total(coll) - hidden) * 1e-9 / trace.window_s
