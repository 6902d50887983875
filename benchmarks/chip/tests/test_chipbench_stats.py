"""Percentile, tail and rate arithmetic of the end-to-end metrics."""
import pytest

from chipbench import stats


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def _rec(due, first=None, finish=None, n_out=10, ok=True):
    return {"due": due, "first": first, "finish": finish, "n_out": n_out,
            "ok": ok}


def test_failed_and_unfinished_requests_count_as_missing():
    # 19 fast requests and one that failed: the failed one is the p95
    recs = [_rec(float(i), first=i + 0.1, finish=i + 1.0)
            for i in range(19)]
    recs.append(_rec(5.5, ok=False))
    ttft = stats.ttft_ms(recs, (0.0, 20.0), end=30.0)
    assert len(ttft) == 20
    assert stats.percentile(ttft, 95) == pytest.approx(100.0)
    assert max(ttft) == pytest.approx(1e3 * (30.0 - 5.5))
    # two missing of twenty: the 95th percentile is a missing one
    recs[0] = _rec(0.0, ok=False)
    ttft = stats.ttft_ms(recs, (0.0, 20.0), end=30.0)
    assert stats.percentile(ttft, 95) == pytest.approx(1e3 * 24.5)


def test_window_selects_by_due_time():
    recs = [_rec(-1.0, 0.0, 1.0), _rec(0.0, 0.2, 1.0), _rec(9.99, 10.5, 11),
            _rec(10.0, 10.1, 10.2)]
    assert len(stats.ttft_ms(recs, (0.0, 10.0), end=20.0)) == 2


def test_tpot():
    recs = [_rec(0.0, first=1.0, finish=2.0, n_out=11),
            _rec(0.0, first=1.0, finish=1.0, n_out=1),  # one token: none
            _rec(0.0, ok=False)]
    assert stats.tpot_ms(recs, (0.0, 1.0)) == [pytest.approx(100.0)]


def test_output_tokens_in_window():
    firsts = [0.5, 1.0, 1.5, 2.0, None]
    assert stats.output_tokens(100, firsts, (1.0, 2.0)) == 102

