"""Metric arithmetic over the per-request stamps the harness takes itself.

Every time here is on the host's ``time.perf_counter`` clock.  A request's
``due`` is when the traffic meant it to be sent (not when the engine
pumped it), its ``tokens`` the stamps at which each output token became
visible to the serving loop, and the window is ``[t0, t1]``.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np


def percentile(values: Sequence[float], p: float) -> float | None:
    """The ``p``-th percentile by linear interpolation between closest
    ranks; ``None`` for no values."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), p))


def due_in(reqs: Iterable, t0: float, t1: float) -> list:
    return [r for r in reqs if t0 <= r.due < t1]


def censored_waits(reqs: Iterable, t0: float, t1: float, stamp: str,
                   close: float | None = None) -> list[float]:
    """For every request due in ``[t0, t1)``, the time from its due time
    to ``stamp`` (an attribute holding a clock reading or ``None``).  A
    request that has not reached the stamp by ``close`` (default ``t1``,
    the window's end) enters with its wait so far; none is dropped."""
    close = t1 if close is None else close
    out = []
    for r in due_in(reqs, t0, t1):
        t = getattr(r, stamp)
        out.append((close if t is None or t > close else t) - r.due)
    return out


def tokens_in(reqs: Iterable, t0: float, t1: float) -> int:
    """Output tokens that became visible inside ``(t0, t1]``."""
    return sum(1 for r in reqs for t in r.tokens if t0 < t <= t1)


def gaps_in(reqs: Iterable, t0: float, t1: float) -> list[float]:
    """Every gap between consecutive output tokens of one request, both
    tokens inside ``[t0, t1]``, over all requests."""
    out = []
    for r in reqs:
        ts = [t for t in r.tokens if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


