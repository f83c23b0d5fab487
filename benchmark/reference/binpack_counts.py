"""Plain reference for batch jobs: greedy BestFit in counts form.

One job = K identical asks. Greedy BestFit places each ask on the
feasible node whose fitness AFTER the placement is highest. A node's
fitness depends on its own usage only and rises as it fills, so the
winner keeps winning until it is full: the K-step greedy collapses to
"fill the best node to capacity, rescore, repeat", a handful of O(N)
steps a job. Jobs are taken in submit order on one usage array.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.fitness import bestfit


def place_job(cap: np.ndarray, used: np.ndarray, ask: np.ndarray,
              k: int) -> np.ndarray:
    """Place up to `k` asks greedily; mutates `used` -> per-node counts.
    Scores and room are computed once for the job and then kept up to
    date for the one node each step touches."""
    counts = np.zeros(len(cap), np.int64)
    left = int(k)
    with np.errstate(divide="ignore", invalid="ignore"):
        room = np.floor(np.min((cap - used) / ask, axis=1) + 1e-9)
    room = np.where(np.isfinite(room), room, 0).astype(np.int64)
    score = np.where(room > 0, bestfit(cap, used + ask), -np.inf)
    while left > 0:
        best = int(np.argmax(score))
        if room[best] <= 0:
            break
        take = min(left, int(room[best]))
        counts[best] += take
        used[best] += take * ask
        left -= take
        room[best] -= take
        score[best] = (bestfit(cap[best], used[best] + ask)
                       if room[best] > 0 else -np.inf)
    return counts
