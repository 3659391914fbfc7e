"""Metric arithmetic: pro-rata counting, percentiles, tpot."""

import pytest

from rtbench import stats


def test_a_request_half_inside_counts_half():
    assert stats.pro_rata_tokens([(5.0, 15.0, 2000)], 10.0, 60.0) == 1000
    assert stats.pro_rata_tokens([(55.0, 65.0, 2000)], 10.0, 60.0) == 1000
    assert stats.pro_rata_tokens([(20.0, 30.0, 2000)], 10.0, 60.0) == 2000
    assert stats.pro_rata_tokens([(0.0, 9.0, 2000)], 10.0, 60.0) == 0
    # a request longer than the window gives the window's share of it
    assert stats.pro_rata_tokens([(0.0, 100.0, 1000)], 10.0, 60.0) == 500


def test_whole_request_counting_steps_where_pro_rata_does_not():
    """16 clients in lock step, a 2,000-token request every 10 s each:
    200 tokens/s a client whatever the window's phase. Counting whole
    requests, a window edge that moves by 0.2 s across an end moves the
    count by a whole request a client; pro rata it stays put."""
    reqs = [(10.0 * k + 0.1 * c, 10.0 * (k + 1) + 0.1 * c, 2000)
            for k in range(12) for c in range(16)]
    rates_whole, rates_pro = [], []
    for w0 in (20.05, 20.25, 20.45, 20.65):
        w1 = w0 + 45.0
        rates_whole.append(stats.whole_request_tokens(reqs, w0, w1) / 45.0)
        rates_pro.append(stats.pro_rata_tokens(reqs, w0, w1) / 45.0)
    assert max(rates_pro) - min(rates_pro) < 1e-6
    assert all(r == pytest.approx(3200.0) for r in rates_pro)
    assert max(rates_whole) - min(rates_whole) >= 2 * 2000 / 45.0


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (90, 4.6),
                                    (100, 5.0), (25, 2.0)])
def test_percentile_interpolates_like_numpy(q, want):
    assert stats.percentile([5.0, 1.0, 4.0, 2.0, 3.0], q) == \
        pytest.approx(want)


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tpot_is_last_minus_first_over_tokens_minus_one():
    assert stats.tpot_ms(10.0, 22.7, 128) == pytest.approx(100.0)
    assert stats.tpot_ms(10.0, 10.0, 1) is None
