"""latency.py — the arithmetic from per-request timestamps to the serving
metrics.  Plain Python on plain numbers, so a test can check it by hand.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it.  No
    interpolation, so the result is always a time some request saw."""
    vs = sorted(values)
    if not vs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(vs)))
    return vs[rank - 1]


def ttft_ms(due_s: float, token_times_s, window_s: float) -> float:
    """Milliseconds from the instant the request was due to its first
    token; a request with no token counts as the window's length."""
    if not token_times_s:
        return window_s * 1e3
    return (token_times_s[0] - due_s) * 1e3


def tpot_ms(token_times_s, finished: bool, window_s: float) -> float:
    """Per request: (last token - first token) / (tokens - 1), in ms.  The
    engine hands over `steps_per_tick` tokens at once, so raw gaps read
    0, 0, 0, big; the mean over the request is what a reader feels.  A
    request that failed or did not finish counts as the window's length."""
    if not finished or len(token_times_s) < 2:
        return window_s * 1e3
    return (token_times_s[-1] - token_times_s[0]) * 1e3 \
        / (len(token_times_s) - 1)


def summarize(records, window_s: float, q: float = 90.0) -> dict:
    """`records`: one dict a request due in the window, with `due` (s),
    `times` (arrival of each token, s), `finished` (bool), `sent` (when
    add_request ran) and optionally `admit`.  Returns the q-th percentiles
    and the counts they stand on."""
    ttft = [ttft_ms(r["due"], r["times"], window_s) for r in records]
    tpot = [tpot_ms(r["times"], r["finished"], window_s) for r in records]
    lag = [(r["sent"] - r["due"]) * 1e3 for r in records
           if r.get("sent") is not None]
    wait = [(r["admit"] - r["due"]) * 1e3 for r in records
            if r.get("admit") is not None]
    out = {"n": len(records),
           "failed": sum(1 for r in records if not r["finished"]),
           "ttft_ms": percentile(ttft, q), "tpot_ms": percentile(tpot, q),
           "ttft_p50_ms": percentile(ttft, 50),
           "tpot_p50_ms": percentile(tpot, 50)}
    if lag:
        out["gen_lag_ms"] = percentile(lag, q)
    if wait:
        out["queue_wait_ms"] = percentile(wait, q)
    return out
