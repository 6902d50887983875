"""The traffic generator: one seed, one schedule; every seed the same
sizes and gaps in the measured window, in another order; lengths and
rates as the mix declares them."""
import numpy as np
import pytest

from chipbench import spec, traffic

CHAT = spec.load_cell("qwen1.5-0.5b.chat").traffic
DOCS = spec.load_cell("qwen3-8b-12l.docs").traffic
WINDOW = (30.0, 51.0)  # (start, length) of the measured window


def _sched(mix, seed, **kw):
    kw.setdefault("window", WINDOW)
    kw.setdefault("horizon_s", 200.0)
    kw.setdefault("min_requests", 256)
    return traffic.schedule(mix, seed, 151936, **kw)


def _in(s, lo, hi):
    return [r for r in s.requests if lo <= r.offset_s < hi]


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["chat", "docs"])
def test_same_seed_same_schedule(mix):
    a, b = _sched(mix, 2**31 + 7), _sched(mix, 2**31 + 7)
    assert [(r.prompt_len, r.out_len, r.offset_s) for r in a.requests] == \
        [(r.prompt_len, r.out_len, r.offset_s) for r in b.requests]
    assert a.prompt(a.requests[5]) == b.prompt(b.requests[5])
    c = _sched(mix, 2**31 + 8)
    assert a.prompt(a.requests[5]) != c.prompt(c.requests[5])


def test_every_seed_sends_the_window_the_same_work():
    mix = dict(CHAT, arrivals={"kind": "poisson", "rate_per_s": 0.5})
    start, length = WINDOW
    k = round(0.5 * length)
    seen = []
    for seed in (1, 2, 3 * 2**31):
        win = _in(_sched(mix, seed), start, start + length)
        assert len(win) == k and win[0].offset_s == start
        seen.append(win)
    for win in seen[1:]:
        assert sorted(r.prompt_len for r in win) == \
            sorted(r.prompt_len for r in seen[0])
        assert sorted(r.out_len for r in win) == \
            sorted(r.out_len for r in seen[0])
        gaps = np.diff([r.offset_s for r in win] + [start + length])
        gaps0 = np.diff([r.offset_s for r in seen[0]] + [start + length])
        assert np.allclose(sorted(gaps), sorted(gaps0))
    assert [r.prompt_len for r in seen[0]] != [r.prompt_len for r in seen[1]]
    # blocks of the same load before and after it: the ramp holds the
    # part of the block before that falls after the load's start
    s = _sched(mix, 1)
    assert 0 < len(_in(s, 0.0, start)) <= k
    assert len(_in(s, start + length, start + 2 * length)) == k


def test_a_closed_loop_sends_the_same_work_per_block():
    k = DOCS["block"]
    assert k % DOCS["sub_block"] == 0
    blocks = []
    for seed in (1, 2, 3 * 2**31):
        reqs = _sched(DOCS, seed).requests[:2 * k]
        blocks.append([sorted(r.prompt_len for r in reqs[i:i + k])
                       for i in (0, k)] +
                      [sorted(r.out_len for r in reqs[i:i + k])
                       for i in (0, k)])
    assert blocks[0] == blocks[1] == blocks[2]


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["chat", "docs"])
def test_lengths_follow_the_declared_distribution(mix):
    if mix["arrivals"]["kind"] == "closed":
        reqs = _sched(mix, 11, min_requests=640).requests[:640]
    else:  # one window of 640 requests
        mix = dict(mix, arrivals={"kind": "poisson", "rate_per_s": 10.0})
        reqs = _in(_sched(mix, 11, window=(0.0, 64.0)), 0.0, 64.0)
    assert len(reqs) == 640
    for key, attr in (("prompt_tokens", "prompt_len"),
                      ("output_tokens", "out_len")):
        d = mix[key]
        x = np.array([getattr(r, attr) for r in reqs])
        assert x.min() >= d["min"] and x.max() <= d["max"]
        assert abs(np.median(x) - d["median"]) <= 0.03 * d["median"]
        q25, q75 = np.percentile(np.log(x), [25, 75])
        # interquartile range of a lognormal: 2 * 0.6745 * sigma
        assert abs((q75 - q25) / (2 * 0.6745) - d["sigma"]) < 0.05


def test_open_loop_rate():
    mix = dict(CHAT, arrivals={"kind": "poisson", "rate_per_s": 5.0})
    s = _sched(mix, 3, window=(10.0, 64.0), horizon_s=640.0)
    assert not s.closed
    t = np.array([r.offset_s for r in s.requests])
    assert np.all(np.diff(t) > 0) and t[0] >= 0.0
    # each window-long block holds exactly rate * window requests
    assert len(_in(s, 10.0, 74.0)) == 320
    assert len(_in(s, 74.0, 138.0)) == 320
    gaps = np.diff(t[(t >= 10.0) & (t < 74.0)])
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.1  # exponential CV


def test_gamma_arrivals_are_burstier():
    mix = dict(CHAT, arrivals={"kind": "gamma", "rate_per_s": 5.0,
                               "cv": 3.0})
    s = _sched(mix, 3, window=(0.0, 64.0))
    t = np.array([r.offset_s for r in _in(s, 0.0, 64.0)])
    assert len(t) == 320
    gaps = np.diff(t)
    assert np.std(gaps) / np.mean(gaps) > 2.0


def test_closed_loop():
    s = _sched(DOCS, 5, min_requests=100)
    assert s.closed and s.clients == 32
    assert all(r.offset_s is None for r in s.requests)
    assert len(s.requests) >= 100



def test_a_closed_loop_is_stratified_within_each_block():
    k, sub = DOCS["block"], DOCS["sub_block"]
    lengths = sorted(r.prompt_len for r in _sched(DOCS, 1).requests[:k])
    stratum = {}  # prompt length -> its stratum (runs of k // sub)
    for i, n in enumerate(lengths):
        stratum.setdefault(n, set()).add(i // (k // sub))
    for seed in (1, 2, 3 * 2**31):
        reqs = _sched(DOCS, seed).requests[:2 * k]
        for i in range(0, 2 * k, sub):
            seen = [stratum[r.prompt_len] for r in reqs[i:i + sub]]
            # one request from each stratum (a length may sit on the
            # boundary of two)
            assert set.union(*seen) >= set(range(sub))
            assert len(reqs[i:i + sub]) == sub


@pytest.mark.parametrize("run", [22, 27, 32])
@pytest.mark.parametrize("key,attr", [("prompt_tokens", "prompt_len"),
                                      ("output_tokens", "out_len")])
def test_any_run_of_a_closed_loop_holds_nearly_the_same_work(run, key,
                                                             attr):
    """A window receives consecutive requests from no fixed start: their
    total length differs by seed and start far less than it would over
    shuffled blocks."""
    k = DOCS["block"]
    q = traffic._quantiles(DOCS[key], k)
    tot, shuffled = [], []
    for seed in range(12):
        reqs = _sched(DOCS, seed).requests
        rng = np.random.default_rng(seed)
        flat = np.concatenate([q[rng.permutation(k)] for _ in range(3)])
        for start in range(25, 60, 5):
            tot.append(sum(getattr(r, attr)
                           for r in reqs[start:start + run]))
            shuffled.append(flat[start:start + run].sum())
    assert np.std(tot) / np.mean(tot) < 0.5 * np.std(shuffled) / np.mean(
        shuffled)


@pytest.mark.parametrize("sub", [5, 3])
def test_sub_block_has_to_be_even_and_divide_the_block(sub):
    with pytest.raises(ValueError, match="does not divide"):
        _sched(dict(DOCS, sub_block=sub), 1)
