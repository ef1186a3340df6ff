"""Tail arithmetic over the window's requests.

A request that arrived in the window and had no first token by its close
enters the time-to-first-token tail at its wait so far (censored at the
close): it is never dropped, so a stalled server cannot look fast by
finishing fewer requests.
"""
from __future__ import annotations

from typing import Iterable, List

import numpy as np


def percentile(values: Iterable[float], q: float) -> float:
    """The ``q``th percentile (linear interpolation between ranks)."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(v, q))


def p95(values: Iterable[float]) -> float:
    return percentile(values, 95)


def ttft(records, close: float) -> List[float]:
    """Seconds from scheduled arrival to first token, for every request
    that arrived by ``close``; one still waiting counts ``close - at``."""
    out = []
    for r in records:
        if r.at > close:
            continue
        out.append((r.first if r.first is not None else close) - r.at)
    return out


def queue_waits(records, close: float) -> List[float]:
    """Seconds from scheduled arrival to the start of the step that
    admitted the request; one never admitted counts ``close - at``."""
    out = []
    for r in records:
        if r.at > close:
            continue
        out.append((r.admitted if r.admitted is not None else close)
                   - r.at)
    return out
