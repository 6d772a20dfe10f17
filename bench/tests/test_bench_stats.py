"""Exact percentiles and the window's accounting."""
import math

import pytest

from bench import stats
from bench.stats import Served, Window


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([5, 1, 4, 2, 3], 95) == 5
    assert stats.percentile([1, 2, math.inf], 50) == 2
    assert stats.percentile([1, 2, math.inf], 95) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _served():
    w = Window(10.0, 20.0)
    return w, [
        # due before the window: its tokens count, its TTFT does not
        Served(due=9.0, sent=9.0, token_times=[9.5, 10.5, 11.0], ok=True,
               admitted=9.2),
        # due inside; last token after the close
        Served(due=12.0, sent=12.0, token_times=[12.5, 13.0, 19.9, 20.5],
               ok=True, admitted=12.1),
        # due inside, failed: TTFT inf, no tokens
        Served(due=15.0, sent=15.0, token_times=[], ok=False),
        # due inside, first token after the close (late, not wrong)
        Served(due=19.0, sent=19.0, token_times=[21.0], ok=True,
               admitted=19.5),
        # due after the window
        Served(due=20.0, sent=20.0, token_times=[20.1], ok=True),
    ]


def test_tokens_and_gaps_inside_the_window():
    w, s = _served()
    assert stats.output_tokens(s, w) == 2 + 3
    assert sorted(stats.gaps(s, w)) == pytest.approx([0.5, 0.5, 6.9])


def test_ttft_counts_every_request_due_inside_and_failures_as_inf():
    w, s = _served()
    assert [x.due for x in stats.due_in(s, w)] == [12.0, 15.0, 19.0]
    assert stats.ttfts(s, w) == pytest.approx([0.5, math.inf, 2.0])
    assert stats.percentile(stats.ttfts(s, w), 95) == math.inf
    assert stats.queue_waits(s, w) == pytest.approx([0.1, 0.5])


def test_window_holds_its_start_not_its_end():
    w = Window(1.0, 2.0)
    assert w.holds(1.0) and not w.holds(2.0) and w.seconds == 1.0


def test_a_closed_loop_request_still_decoding_is_late_not_failed():
    from types import SimpleNamespace as NS

    from bench.serving import DRAIN_S, Run

    def failed(closed, last_token):
        run = NS(closed=closed, window=Window(0.0, 50.0))
        s = Served(due=None if closed else 10.0, sent=10.0,
                   token_times=[11.0, last_token], ok=None)
        run.counted = lambda: [s]
        return Run.failed(run)

    end = 50.0 + DRAIN_S
    assert failed(closed=True, last_token=end - 1) == 0     # still decoding
    assert failed(closed=True, last_token=end - 30) == 1    # stalled
    assert failed(closed=False, last_token=end - 1) == 1    # owed, unanswered
