"""Tail and rate arithmetic: percentiles over all requests, timing from
the due time, a window that closes at a step boundary."""
import numpy as np
import pytest

from bench import stats


@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_percentile_matches_numpy_over_all_values(q):
    xs = list(np.random.default_rng(q).lognormal(size=101))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_ttft_counts_from_due_not_submit():
    reqs = [{"rid": 0, "due": 1.0, "submitted": 3.0, "token_times": [4.0]},
            {"rid": 1, "due": 2.0, "submitted": 2.0, "token_times": [2.5]},
            {"rid": 2, "due": 5.0, "submitted": 5.0, "token_times": []}]
    assert stats.ttfts(reqs, settled=9.0) == [3.0, 0.5, 4.0]


def test_gaps_cover_every_request_up_to_the_close():
    reqs = [{"token_times": [1.0, 1.5, 2.5, 9.0]},
            {"token_times": [2.0, 2.0, 3.0]}]
    assert sorted(stats.gaps(reqs, 0.0, close=5.0)) == [0.0, 0.5, 1.0, 1.0]
    # a gap counts where its later token falls inside the window
    assert sorted(stats.gaps(reqs, 1.5, close=5.0)) == [0.0, 1.0, 1.0]


def test_tokens_in_window_excludes_the_edges_before_open():
    reqs = [{"token_times": [1.0, 2.0, 3.0]}, {"token_times": [2.0, 4.0]}]
    assert stats.tokens_in(reqs, open_t=1.0, close=3.0) == 3
