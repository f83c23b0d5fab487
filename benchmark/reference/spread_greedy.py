"""Plain reference for service jobs: per-placement greedy with the even
spread boost, job anti-affinity and dynamic-port feasibility.

Each placement goes to the feasible node with the highest mean of the
score terms present, as upstream's rank.go / spread.go define them:

- BestFit fitness of the node after the placement (always present);
- job anti-affinity -(held + 1) / count once the node already holds
  `held` allocations of this job;
- even-spread boost over the attribute's values SEEN so far (upstream
  evenSpreadScoreBoost: a value not yet used scores +1 against used
  ones, the fullest scores negative, all-equal scores -1 for everyone).

A dynamic port is feasible while the node has a free one in the
dynamic range. Between two placements only one node's fitness and one
value's count change, so each step is O(N) cheap vector work.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.fitness import bestfit

DYNAMIC_PORTS = 32000 - 20000 + 1


def even_boost(value_counts: np.ndarray) -> np.ndarray:
    """Boost per attribute value given the job's count on each value
    (0 = value not seen yet)."""
    seen = value_counts > 0
    if not seen.any():
        return np.zeros(len(value_counts))
    c = value_counts.astype(np.float64)
    lo, hi = c[seen].min(), c[seen].max()
    at_min = (-1.0 if lo == hi else (hi - lo) / lo)
    return np.where(c != lo, (lo - c) / lo, at_min)   # unseen (c = 0): +1


def place_job(cap, used, ports_used, value_of, n_values, job) -> dict:
    ask = np.array([job["cpu"], job["mem"]], np.float64)
    k, want_port = int(job["count"]), int(job.get("ports", 0))
    spread = bool(job.get("spread"))
    held = np.zeros(len(cap), np.int64)
    per_value = np.zeros(n_values, np.int64)
    # fit + anti-affinity term, and the number of terms, per node; -inf
    # marks a node with no room or no free port. One node changes a step.
    ok = np.all(used + ask <= cap, axis=1) \
        & (ports_used + want_port <= DYNAMIC_PORTS)
    base = np.where(ok, bestfit(cap, used + ask), -np.inf)
    terms = np.ones(len(cap))
    placed = 0
    for _ in range(k):
        if spread:
            boost = even_boost(per_value)
            score = (base + boost[value_of]) / (terms
                                               + (boost != 0.0)[value_of])
        else:
            score = base / terms
        best = int(np.argmax(score))
        if score[best] == -np.inf:
            break
        used[best] += ask
        ports_used[best] += want_port
        held[best] += 1
        per_value[value_of[best]] += 1
        placed += 1
        if (np.all(used[best] + ask <= cap[best])
                and ports_used[best] + want_port <= DYNAMIC_PORTS):
            base[best] = (bestfit(cap[best], used[best] + ask)
                          - (held[best] + 1.0) / k)
            terms[best] = 2.0
        else:
            base[best] = -np.inf
    return {"held": held, "per_value": per_value, "placed": placed}


def run(cap: np.ndarray, used0: np.ndarray, value_of: np.ndarray,
        n_values: int, jobs: list) -> dict:
    """jobs: [{"count", "cpu", "mem", "ports", "spread"}] in submit
    order; `value_of[i]` is node i's spread-attribute value index ->
    {"counts", "used", "unplaced", "per_value": [per job]}."""
    used = used0.astype(np.float64).copy()
    cap = cap.astype(np.float64)
    ports_used = np.zeros(len(cap), np.int64)
    counts = np.zeros(len(cap), np.int64)
    per_value, unplaced = [], 0
    for job in jobs:
        got = place_job(cap, used, ports_used, value_of, n_values, job)
        counts += got["held"]
        per_value.append(got["per_value"])
        unplaced += int(job["count"]) - got["placed"]
    return {"counts": counts, "used": used, "unplaced": unplaced,
            "per_value": per_value}
