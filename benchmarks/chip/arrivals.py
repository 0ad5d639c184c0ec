"""Open-loop request schedules drawn from a seed.

Copied in spirit from the program's ``workloads/arrivals.poisson_arrivals``
(exponential gaps at a fixed rate) so that the yardstick cannot move with
the program, with one change for a benchmark: every seed gets the same set
of gaps and the same count of each request class, in another order. The
gaps are the exponential distribution's quantiles at (i + 1/2) / n, scaled
so that the n arrivals span the window; the seed shuffles them and the
classes. Two seeds then offer the same work, and their runs differ by the
order of arrivals and by the tokens, not by how many requests came.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def poisson_schedule(rate: float, seconds: float, shares: Sequence[float],
                     rng: np.random.Generator) -> List[Tuple[float, int]]:
    """[(due second, class index)] for ``round(rate * seconds)`` requests
    due in [0, seconds), sorted by due time. Class i gets
    ``round(share_i * n)`` requests (the last class takes the rest)."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds} s offers no request")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    counts = [int(round(sh * n)) for sh in shares[:-1]]
    counts.append(n - sum(counts))
    if min(counts) < 0:
        raise ValueError(f"class shares {shares} do not fit {n} requests")
    classes = rng.permutation(np.repeat(np.arange(len(shares)), counts))
    return [(float(t), int(c)) for t, c in zip(due, classes)]
