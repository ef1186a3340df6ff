"""Tail arithmetic: censored requests stay in the tail."""
from types import SimpleNamespace as R

import numpy as np
import pytest

from bench import tails


def test_p95_matches_numpy():
    x = list(np.linspace(0.0, 1.0, 101))
    assert tails.p95(x) == pytest.approx(0.95)
    with pytest.raises(ValueError):
        tails.p95([])


def test_ttft_counts_a_request_still_waiting_at_its_wait_so_far():
    recs = [R(at=0.0, first=0.2, admitted=0.1),
            R(at=1.0, first=None, admitted=None),     # never served
            R(at=9.5, first=None, admitted=9.6),      # admitted, no token
            R(at=11.0, first=None, admitted=None)]    # after the close
    assert tails.ttft(recs, 10.0) == pytest.approx([0.2, 9.0, 0.5])
    assert tails.queue_waits(recs, 10.0) == pytest.approx([0.1, 9.0, 0.1])


def test_censoring_moves_the_tail():
    served = [R(at=0.0, first=0.1, admitted=0.0) for _ in range(19)]
    stuck = [R(at=0.0, first=None, admitted=None)]
    # one in twenty never gets a token: the p95 sits between the served
    # 0.1 s and the stuck request's 10 s, never at the served value
    p = tails.p95(tails.ttft(served + stuck, 10.0))
    assert 0.1 < p < 10.0
    assert tails.p95(tails.ttft(served, 10.0)) == pytest.approx(0.1)
