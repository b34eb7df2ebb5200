import pytest

from bench import stats
from bench.loadgen import Record


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3
    # a tail over 20 values is the 19th: one value lies beyond it
    assert stats.percentile(range(20), 95) == 18
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _rec(submit, done, ok=True):
    return Record(client=0, index=0, params={}, submit_t=submit,
                  done_t=done, result=object() if ok else None)


def test_completions_and_rate_cover_the_whole_window():
    recs = [_rec(0.0, 0.5), _rec(1.0, 1.5), _rec(2.0, 9.0),
            _rec(1.0, 2.0, ok=False), _rec(3.0, None)]
    done = stats.completed_in(recs, 0.0, 4.0)
    assert [r.done_t for r in done] == [0.5, 1.5]
    # the rate divides by the window, not by the span of the completions
    assert stats.rate([10.0, 30.0], 4.0) == 10.0
    with pytest.raises(ValueError):
        stats.rate([1.0], 0.0)


def test_drain_end_waits_for_every_request_of_the_window():
    recs = [_rec(0.0, 0.5), _rec(3.5, 4.25), _rec(3.0, None)]
    assert stats.drain_end(recs, 4.0) == 4.25
    # nothing came back after the close: the window ends where it closed
    assert stats.drain_end(recs[:1], 4.0) == 4.0
    assert stats.drain_end([], 4.0) == 4.0
