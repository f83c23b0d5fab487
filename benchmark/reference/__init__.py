"""The plain references `correct` compares the system with. Numpy only;
nothing here imports the program."""

from __future__ import annotations

import numpy as np

from benchmark.reference import binpack_counts, spread_greedy
from benchmark.reference.fitness import mean_fitness


def reference_fitness(cap: np.ndarray, used0: np.ndarray,
                      value_of: np.ndarray, n_values: int,
                      jobs: list) -> dict:
    """Run `jobs` (specs, submit order) through the reference that fits
    each: counts form for a job with no port and no spread, per
    placement otherwise, on one shared usage array -> {"fitness",
    "unplaced", "counts", "used"}."""
    used = used0.astype(np.float64).copy()
    counts = np.zeros(len(cap), np.int64)
    ports_used = np.zeros(len(cap), np.int64)
    capf = cap.astype(np.float64)
    unplaced = 0
    for job in jobs:
        if job.get("ports") or job.get("spread"):
            got = spread_greedy.place_job(capf, used, ports_used, value_of,
                                          n_values, job)
            counts += got["held"]
            unplaced += int(job["count"]) - got["placed"]
        else:
            ask = np.array([job["cpu"], job["mem"]], np.float64)
            c = binpack_counts.place_job(capf, used, ask, job["count"])
            counts += c
            unplaced += int(job["count"]) - int(c.sum())
    return {"fitness": mean_fitness(capf, used, counts),
            "unplaced": unplaced, "counts": counts, "used": used}
