"""boundaries.py — what a boundary of the serve loop costs the device, and
why it was one.

A *boundary* is a turn of `ServingEngine._cycle` that starts with nothing
in flight: the tick before was harvested alone, then the schedule, the
chunks and the next tick's dispatch ran in a row with the chip waiting.
The program names each stretch (`serve:schedule` and its leaves,
`serve:tick_dispatch` with `chained` = 0, `serve:harvest_wait`,
`serve:emit`) and says on the `serve:schedule` span `why` the tick before
was not chained (`waiting`, `finished`, `budget_spent`, ...; `idle` when
nothing was in flight).  `program_spans.idle_under_spans_ms` reads the
device-idle time under those names; this file reads what lies between
them and the device:

- `readback_lag_ms`: from a launch's last op — or from where the
  `serve:harvest_wait` that harvested it begins, if that is later: till
  then the host was busy elsewhere, under the spans that say where — to
  that wait's end, the part in which the chip is idle (a chained tick
  keeps it busy): the copy and the host's wake-up;
- `why_share_pct`: the share of boundaries whose `why` is one of a set;
- `unattributed_idle_pct`: the share of device-idle time that lies under
  no `serve:*` span at all — the closing check of the account.

Every reader takes `(trace, counters, args)` and returns a number, or
None where there is nothing to read: no trace, no device plane, a program
without these spans or attrs (the parent of the PR that added them).  The
functions on plain lists are there so that a test can check them by hand.
"""

from __future__ import annotations

import bisect

from benchmark.reducers import program_spans, xplane


def _idle_ns(gaps: list, lo: float, hi: float) -> float:
    """Device-idle ns inside `[lo, hi]`; 0 for an empty interval."""
    return program_spans._overlap_ns(gaps, [(lo, hi)]) if hi > lo else 0.0


def _launches(trace, module: str) -> list:
    """`(start, end)` of the launches of `module` on device 0 that lie
    whole inside the window, by start."""
    lo, hi = trace.window
    return sorted((s, e) for s, e, n
                  in trace.devices[min(trace.devices)]["modules"]
                  if n.split("(")[0] == module and s > lo and e < hi)


def readback_ns(launches: list, ticks: list, waits: list,
                gaps: list) -> tuple:
    """`(readback lag ns, boundary ticks)` summed over `launches`
    `[(start, end)]` of the tick program.

    `ticks` are the `serve:tick_dispatch` spans `(start, end, chained)`
    by start; a launch belongs to the last one begun before it (as
    `program_spans.device_ms_per_step` joins them) and is a boundary's
    where that span has `chained` = 0.  `waits` are the
    `serve:harvest_wait` spans `(start, end)` by end; a launch is
    harvested by the first one that ends after the launch does and
    harvested no launch before it (ticks are harvested in their order,
    one wait each), and its readback lag is the idle time inside that
    wait from the launch's last op on.  `gaps` are the window's
    device-idle intervals."""
    begun = [t[0] for t in ticks]
    readback = 0.0
    boundaries = j = 0
    for s, e in launches:
        i = bisect.bisect_right(begun, s) - 1
        if i >= 0 and not int(ticks[i][2]):
            boundaries += 1
        while j < len(waits) and waits[j][1] <= e:
            j += 1
        if j < len(waits):
            readback += _idle_ns(gaps, max(e, waits[j][0]), waits[j][1])
            j += 1
    return readback, boundaries


def readback_lag_ms(trace, counters, args):
    """Device-idle time inside the `serve:harvest_wait` that harvested a
    launch, from the launch's last op on, summed over the launches of
    `args["module"]` in the window (0 where a chained tick keeps the chip
    busy) over its boundary ticks, in ms."""
    if trace is None or not trace.devices:
        return None
    spans = program_spans.spans_with_attrs(trace, in_window=False)
    ticks = [(s, e, a.get("chained", 0)) for s, e, n, a in spans
             if n == "serve:tick_dispatch"]
    waits = sorted(((s, e) for s, e, n, _ in spans
                    if n == "serve:harvest_wait"), key=lambda w: w[1])
    lag, boundaries = readback_ns(_launches(trace, args["module"]), ticks,
                                  waits, program_spans.idle_gaps_of(trace))
    return lag * 1e-6 / boundaries if boundaries else None


def why_share(whys: list, of: list):
    """Share (0..1) of these boundaries' `why` words that are in `of`;
    `idle` (nothing was in flight) is no boundary of a tick and is left
    out; None where nothing is left."""
    real = [w for w in whys if w != "idle"]
    return sum(1 for w in real if w in of) / len(real) if real else None


def why_share_pct(trace, counters, args):
    """Share of the window's `serve:schedule` spans that followed a tick
    (`why` is not `idle`) whose `why` is in `args["why"]`, in %."""
    share = why_share([a["why"] for _, _, n, a
                       in program_spans.spans_with_attrs(trace)
                       if n == "serve:schedule" and "why" in a],
                      args["why"])
    return None if share is None else 100.0 * share


def unattributed_idle_pct(trace, counters, args):
    """Device-idle time of the window under no `serve:*` span of the
    program, over all its device-idle time, in %: loop glue, the tail of
    a harvest behind `serve:emit`, another thread holding the
    interpreter."""
    if trace is None or not trace.devices:
        return None
    named = xplane.union(x for x in trace.host if x[2].startswith("serve:"))
    gaps = program_spans.idle_gaps_of(trace)
    idle = sum(e - s for s, e in gaps)
    if not named or idle <= 0:
        return None
    return 100.0 * (1.0 - program_spans._overlap_ns(gaps, named) / idle)
