"""engine_counts.py — readers of what the job counted itself: timestamps
of its own requests and the engine's per-request stamps.  Each takes
`(trace, counters, args)` and returns a number, or None where the job
counted nothing of the kind."""

from __future__ import annotations


def counter(trace, counters, args):
    """The counter named `args["key"]`, as the job reported it."""
    v = counters.get(args["key"])
    return None if v is None else float(v)
