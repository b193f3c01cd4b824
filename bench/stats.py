"""What the metric readers share: latencies of the window's requests,
in seconds, on the host clock, each request timed from when it was due."""
from __future__ import annotations

from typing import List

import numpy as np


def percentile(values, q: float):
    """The ``q``-th percentile, or None where there is nothing to read."""
    return float(np.percentile(values, q)) if len(values) else None


def ttfts(run) -> List[float]:
    """Due time to first host-visible token, for every window request
    that got one."""
    return [s.token_times[0] - s.due for s in run.window_requests
            if s.token_times]


def queue_waits(run) -> List[float]:
    """Due time to the start of the step that gave the request a slot."""
    return [s.admitted - s.due for s in run.window_requests
            if s.admitted is not None]


def token_gaps(run) -> List[float]:
    """Gaps between successive host-visible tokens of a request. Below
    the knee: every gap of every window request. Above it the queue never
    drains, so only gaps that lie inside the window count, of any
    request."""
    out: List[float] = []
    if run.over:
        for s in run.requests:
            t = [x for x in s.token_times if run.t0 <= x <= run.t_end]
            out.extend(np.diff(t).tolist())
    else:
        for s in run.window_requests:
            out.extend(np.diff(s.token_times).tolist())
    return out


def window_tokens(run) -> int:
    """Tokens that reached the host inside the window, of any request."""
    return sum(1 for s in run.requests for t in s.token_times
               if run.t0 <= t <= run.t_end)
