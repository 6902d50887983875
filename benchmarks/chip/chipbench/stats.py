"""Percentiles and window arithmetic of the end-to-end metrics."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``%
    of the values at or below it.  ``inf`` values sort last."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def ttft_ms(requests, window, end: float) -> list:
    """Time to first token of every request due in ``window`` = (start,
    stop), from its due time.  A request with no first token by ``end``
    (failed, or not done by the drain cap) counts as missing: its time is
    ``end`` minus its due time, a floor under the time it would have
    taken, so it ranks behind every request that made it."""
    lo, hi = window
    out = []
    for r in requests:
        if not lo <= r["due"] < hi:
            continue
        first = r["first"] if r["ok"] else None
        out.append(1e3 * ((first if first is not None else end) - r["due"]))
    return out


def tpot_ms(requests, window) -> list:
    """(finish - first token) / (outputs - 1) of every completed request
    due in ``window`` with more than one output."""
    lo, hi = window
    return [1e3 * (r["finish"] - r["first"]) / (r["n_out"] - 1)
            for r in requests
            if lo <= r["due"] < hi and r["ok"] and r["n_out"] > 1]


def output_tokens(decode_tokens_delta: int, first_token_times,
                  window) -> int:
    """Output tokens generated in ``window``: the engine's decode-token
    counter over the window plus the first tokens (made by prefill, which
    that counter leaves out) stamped inside it."""
    lo, hi = window
    return decode_tokens_delta + sum(1 for t in first_token_times
                                     if t is not None and lo <= t < hi)
