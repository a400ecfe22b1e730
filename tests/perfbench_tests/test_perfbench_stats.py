import math

import pytest

from perfbench.harness import stats as S


@pytest.mark.parametrize("vals,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4, 5], 100, 5.0),
    ([10], 95, 10.0), ([0, 10], 95, 9.5), (list(range(101)), 95, 95.0),
    ([], 95, None)])
def test_percentile(vals, q, want):
    assert S.percentile(vals, q) == want


def test_percentile_keeps_the_unanswered_in_the_tail():
    vals = [1.0] * 18 + [math.inf, math.inf]
    assert S.percentile(vals, 95) == math.inf
    assert S.percentile(vals, 50) == 1.0


def test_unfinished_request_counts_as_worst():
    reqs = [{"t_submit": 1.0, "token_times": [1.5, 1.6]},
            {"t_submit": 2.0, "token_times": [], "failed": False},
            {"t_submit": 3.0, "token_times": [3.1], "failed": True}]
    got = S.ttft_samples(reqs, worst=10.0)
    assert got == [0.5, 8.0, 7.0]


def test_itl_is_every_gap_of_every_request():
    reqs = [{"token_times": [1.0, 1.5, 2.5]}, {"token_times": [4.0]},
            {"token_times": [5.0, 5.25]}]
    assert S.itl_samples(reqs) == [0.5, 1.0, 0.25]


def test_tokens_in_window_counts_warmup_requests_too():
    reqs = [{"token_times": [0.5, 1.0, 1.5]}, {"token_times": [1.9, 2.0]}]
    assert S.tokens_in_window(reqs, 1.0, 2.0) == 3


def test_step_in_flight_is_finished_and_counted():
    # window of 1.0 s from t=10: the third step was dispatched at 10.9
    # and completed at 11.3 -- it counts, and so does its time
    done = [10.4, 10.8, 11.3]
    rate = S.train_rate(done, 10.0, tokens_per_step=100, chips=2)
    assert rate == pytest.approx(3 * 100 / 1.3 / 2)
    assert S.train_rate([], 10.0, 100, 1) is None


def test_longest():
    assert S.longest([0.1, 0.5, 0.2]) == (1, 0.5)
    assert S.longest([]) == (None, None)
