"""openloop.py — the one traffic generator.  A mix is a data file of
parameters under `benchmark/traffic/`; this module turns a mix and a seed
into the inputs a job feeds the program.  The program sees only those.

Two kinds of mix:

* `"kind": "requests"` — an open loop of independent users: arrivals on a
  schedule fixed before the run (Poisson, or gamma inter-arrival times
  with a coefficient of variation for bursts), prompt and output lengths
  log-normal around a median and clipped, random token ids, optionally a
  share of each prompt taken from a few shared prefixes.
* `"kind": "tokens"` — batches for training: a token stream with structure
  a model can learn (a skewed unigram distribution and, half the time, a
  fixed successor), cut into `[batch, seq_len]` inputs and next-token
  labels.

Every seed gives the same *set* of sizes and gaps in another order: the
lengths and inter-arrival times are drawn once from the mix's own fixed
seed and only shuffled by `--seed`, so two seeds load the system alike —
and, for requests, the lead-in and the window each keep their own set, so
the window holds the same work whatever the seed.
"""

from __future__ import annotations

import numpy as np

_SET_SEED = 20240924       # draws the set of sizes; never the run's seed


def _rng(seed: int, salt: int = 0) -> np.random.RandomState:
    # --seed may be a little over 2**31; RandomState takes 32 unsigned bits
    return np.random.RandomState((int(seed) * 2654435761 + salt) % (2 ** 32))


def _lognormal_clipped(rng, n, median, sigma, lo, hi):
    x = np.exp(rng.normal(np.log(median), sigma, n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _part(mix: dict, n: int, span_s: float, salt: int, rng) -> tuple:
    """`n` arrivals that fill `span_s`, with their lengths: the set comes
    from the mix's own seed (`salt` tells the parts apart), `rng` — the
    run's — only puts it in another order."""
    base = _rng(_SET_SEED, salt)
    cv = float(mix.get("arrival_cv", 1.0))        # 1 = Poisson
    shape = 1.0 / (cv * cv)
    gaps = base.gamma(shape, 1.0 / shape, n)
    gaps *= span_s / (gaps.sum() + gaps.mean())    # n arrivals fill the span
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    plen = _lognormal_clipped(base, n, p["median"], p["sigma"],
                              p["min"], p["max"])
    olen = _lognormal_clipped(base, n, o["median"], o["sigma"],
                              o["min"], o["max"])
    order = rng.permutation(n)
    return np.cumsum(gaps[rng.permutation(n)]), plen[order], olen[order]


def request_schedule(mix: dict, rate_rps: float, lead_s: float,
                     window_s: float, seed: int, vocab_size: int) -> list:
    """Requests due in `[0, lead_s + window_s)`: a list of dicts with `due`
    (seconds from the schedule's start), `prompt` (token ids) and
    `max_new_tokens`, ordered by `due`.  The lead-in and the window are
    drawn apart, so that every seed puts the same requests — the same
    count, the same lengths — into the window, and only their order, their
    gaps' order and their token ids differ."""
    rng = _rng(seed, 2)
    parts = []
    for salt, start, span in ((5, 0.0, lead_s), (1, lead_s, window_s)):
        n = int(round(rate_rps * span))
        if n:
            due, plen, olen = _part(mix, n, span, salt, rng)
            parts.append((due + start, plen, olen))
    due, plen, olen = (np.concatenate(x) for x in zip(*parts))
    shared = mix.get("shared_prefixes")
    prefixes = []
    if shared:
        prefixes = [rng.randint(1, vocab_size, int(L)).tolist()
                    for L in _lognormal_clipped(
                        _rng(_SET_SEED, 3), shared["count"],
                        shared["median"], shared["sigma"],
                        shared["min"], shared["max"])]
    out = []
    for i in range(len(due)):
        prompt = rng.randint(1, vocab_size, int(plen[i])).tolist()
        if prefixes:
            prompt = prefixes[rng.randint(len(prefixes))] + prompt
        out.append({"due": float(due[i]), "prompt": prompt,
                    "max_new_tokens": int(olen[i])})
    return out


def token_batches(mix: dict, seed: int, vocab_size: int, n_batches: int,
                  leading: tuple = ()) -> tuple:
    """`n_batches` pairs of int32 arrays shaped `leading + (batch,
    seq_len)`: inputs and next-token labels."""
    B, S = int(mix["batch"]), int(mix["seq_len"])
    rng = _rng(seed, 4)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    prob = ranks ** -float(mix.get("zipf_exponent", 1.1))
    prob /= prob.sum()
    ident = rng.permutation(vocab_size)            # rank -> token id
    succ = rng.permutation(vocab_size)             # token -> its successor
    follow = float(mix.get("successor_share", 0.5))
    total = n_batches * int(np.prod(leading, dtype=np.int64)) * B
    draws = ident[rng.choice(vocab_size, (total, S + 1), p=prob)]
    take = rng.random_sample((total, S + 1)) < follow
    seq = draws.copy()
    for t in range(1, S + 1):
        seq[:, t] = np.where(take[:, t], succ[seq[:, t - 1]], draws[:, t])
    seq = seq.reshape((n_batches,) + tuple(leading) + (B, S + 1))
    return (np.ascontiguousarray(seq[..., :-1]).astype(np.int32),
            np.ascontiguousarray(seq[..., 1:]).astype(np.int32))
