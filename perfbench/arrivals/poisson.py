"""Open loop: queries fall due as a Poisson process at the mix's
``rate_qps`` and are sent when due, whatever the server is doing; a
query's latency runs from when it was due.

The gaps are the same set for every seed, in another order: the
exponential distribution's quantiles at ``(i + 0.5) / n``, so the count
of queries in a window never changes with the seed.
"""

from __future__ import annotations

import numpy as np

import loops
from corpus import rng_for

#: latency runs from when a query was due, not from when it was sent
OPEN = True


def arrival_gaps(rate: float, n: int, seed: int) -> np.ndarray:
    """``n`` inter-arrival gaps at ``rate``: the same set for every seed,
    in an order drawn from it."""
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q) / float(rate)
    return rng_for(seed, 4).permutation(gaps)


def open_schedule(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times, from the window's start, of the window's queries."""
    n = max(1, int(round(float(mix["rate_qps"]) * seconds)))
    gaps = arrival_gaps(float(mix["rate_qps"]), n, seed)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due[due < seconds]


def run(driver, mix: dict, stream, seconds: float, seed: int,
        extra=None) -> list:
    """Send the window's queries as they fall due; ``extra`` is not
    read, as the schedule fixes how far the loop runs."""
    due = open_schedule(mix, seconds, seed)
    return loops.open_loop(driver, stream.take(len(due)), due, seconds)


def finish(driver, recs, grace: float) -> None:
    loops.finish_open(driver, recs, grace)


def attempted(recs, end: float) -> int:
    return sum(1 for r in recs if r.due < end)
