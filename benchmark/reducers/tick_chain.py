"""tick_chain.py — how often the serve loop keeps a tick in flight.

`ServingEngine._dispatch_tick` says on every `serve:tick_dispatch` span
whether the tick was `chained`: 1 where it was enqueued on the device
tokens of a tick not yet harvested (the device goes from one tick to the
next without the host), 0 at a boundary, where the schedule ran first and
the device waited for it.  A span without the attr is a boundary's: a
program from before the attr never chained under `serve_forever`.

`chained_share` takes plain dicts so that a test can check it by hand.
The reader takes `(trace, counters, args)` and returns a number, or None
where there is nothing to read (no trace, no such span in the window).
"""

from __future__ import annotations

from benchmark.reducers import program_spans


def chained_share(ticks: list):
    """Share (0..1) of these tick dispatches that were chained; None for
    an empty list."""
    if not ticks:
        return None
    return sum(1 for a in ticks if int(a.get("chained", 0))) / len(ticks)


def tick_chained_pct(trace, counters, args):
    """Share of the window's `args["span"]` spans with `chained` = 1, in
    %: the ticks whose launch the device did not wait for."""
    share = chained_share([a for _, _, n, a
                           in program_spans.spans_with_attrs(trace)
                           if n == args["span"]])
    return None if share is None else 100.0 * share
