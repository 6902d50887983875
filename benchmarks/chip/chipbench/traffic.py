"""The one traffic generator: a mix file's parameters and a seed in, a
schedule of requests out.

Every seed gets the same multiset of sizes and gaps, in another order.
Sizes and gaps are drawn in blocks; each block holds the distribution's
quantiles at ``(i + 0.5) / k`` for its ``k`` requests, and the seed
shuffles each block.

- An open loop's blocks are aligned with the measured window: each block
  holds the ``k = round(rate * window_s)`` requests due in one
  window-long stretch, its gaps scaled to fill that stretch exactly, and
  one block starts where the window does.  So every seed sends the
  window the same requests at the same mean rate, in another order, and
  runs differ by order and token ids alone.
- A closed loop releases its requests in list order, as clients free up,
  so a window receives a run of consecutive requests that no block
  boundary aligns with.  Its blocks of ``block`` requests are therefore
  ordered in strata: every ``sub_block`` consecutive requests of a block
  hold one prompt length and one output length from each
  ``1/sub_block`` of the block's sorted lengths, a short one beside a
  long one.  Any run of requests then holds nearly the same work on
  every seed, while a block still reaches the distribution's tails.

Mix parameters (``traffic/<mix>.json``)::

    arrivals       {"kind": "poisson", "rate_per_s": r}         open loop
                   {"kind": "gamma", "rate_per_s": r, "cv": c}  open loop
                   {"kind": "closed", "clients": n}   no think time
    prompt_tokens  {"dist": "lognormal", "median", "sigma", "min", "max"}
    output_tokens  the same
    block, sub_block   a closed loop's strata (``sub_block`` is even
                   and divides ``block``)

Prompt token ids are uniform over the vocabulary, drawn from the seed and
the request's index, so no two prompts share a prefix.  Outputs are
greedy with a fixed length.
"""
import dataclasses
import math
from statistics import NormalDist
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    prompt_len: int
    out_len: int
    offset_s: Optional[float]  # open loop: due time after the load starts


@dataclasses.dataclass
class Schedule:
    closed: bool
    clients: int
    requests: list  # [Request], in release order
    seed: int
    vocab: int

    def prompt(self, req: Request) -> list:
        """The request's token ids, the same for the same seed."""
        rng = np.random.default_rng([self.seed % 2**63, 1, req.idx])
        return rng.integers(0, self.vocab, req.prompt_len).tolist()


def _quantiles(spec: dict, k: int) -> np.ndarray:
    u = (np.arange(k) + 0.5) / k
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _strata(k: int, sub: int, rng) -> np.ndarray:
    """An order of ``k`` sorted values in which every ``sub`` consecutive
    entries take one value from each of ``sub`` strata (runs of
    ``k // sub`` neighbours), in pairs of opposite strata (the lowest
    with the highest, ...), so that a run of entries that starts anywhere
    holds nearly the same total.  The seed picks each stratum's member,
    the order of the pairs and the order within each pair."""
    if k % sub or sub % 2:
        raise ValueError(f"sub_block {sub} is odd or does not divide "
                         f"block {k}")
    members = rng.permuted(np.arange(k).reshape(sub, k // sub), axis=1)
    lo = np.arange(sub // 2)
    out = []
    for col in members.T:
        pairs = np.column_stack([lo, sub - 1 - lo])[rng.permutation(lo)]
        flip = rng.integers(0, 2, sub // 2).astype(bool)
        pairs[flip] = pairs[flip, ::-1]
        out.append(col[pairs.ravel()])
    return np.concatenate(out)


def _gaps(arrivals: dict, k: int) -> np.ndarray:
    u = (np.arange(k) + 0.5) / k
    rate = float(arrivals["rate_per_s"])
    if arrivals["kind"] == "poisson":
        return -np.log1p(-u) / rate
    if arrivals["kind"] == "gamma":
        from scipy.stats import gamma

        shape = 1.0 / arrivals["cv"] ** 2
        return gamma.ppf(u, shape, scale=1.0 / (rate * shape))
    raise ValueError(f"unknown arrivals {arrivals['kind']!r}")


def schedule(mix: dict, seed: int, vocab: int, *, window: tuple,
             horizon_s: float, min_requests: int = 0) -> Schedule:
    """The requests of one run.  ``window`` = (start, length) of the
    measured window, in seconds from the load's start.  An open loop gets
    every request due before ``horizon_s``; a closed loop gets
    ``min_requests``, enough for its clients to stay busy."""
    arr = mix["arrivals"]
    closed = arr["kind"] == "closed"
    rng = np.random.default_rng([seed % 2**63, 0])
    reqs: list = []

    def add(prompt, out, offset):
        reqs.append(Request(idx=len(reqs), prompt_len=int(prompt),
                            out_len=int(out), offset_s=offset))

    if closed:
        k, sub = int(mix["block"]), int(mix["sub_block"])
        prompts = _quantiles(mix["prompt_tokens"], k)
        outs = _quantiles(mix["output_tokens"], k)
        while len(reqs) < min_requests:
            order = [_strata(k, sub, rng) for _ in range(2)]
            for j in range(k):
                add(prompts[order[0][j]], outs[order[1][j]], None)
        return Schedule(True, int(arr["clients"]), reqs, seed, vocab)
    start, length = window
    k = max(1, round(float(arr["rate_per_s"]) * length))
    prompts = _quantiles(mix["prompt_tokens"], k)
    outs = _quantiles(mix["output_tokens"], k)
    gaps = _gaps(arr, k)
    gaps *= length / gaps.sum()
    t0 = start - math.ceil(start / length) * length
    while t0 < horizon_s:
        order = [rng.permutation(k) for _ in range(3)]
        due = t0 + np.concatenate([[0.0], np.cumsum(gaps[order[2]])[:-1]])
        for j in range(k):
            if due[j] >= 0:
                add(prompts[order[0][j]], outs[order[1][j]], float(due[j]))
        t0 += length
    # one request past the horizon, so the load never runs out
    add(prompts[0], outs[0], t0 + length)
    return Schedule(False, 0, reqs, seed, vocab)
