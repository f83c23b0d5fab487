"""BestFit fitness, written out here so that neither reference nor the
checker shares code with nomad_tpu/tensor/.

Upstream funcs.go ScoreFitBinPack on (cpu, mem): 20 - 10^free_cpu -
10^free_mem, clipped to [0, 18], over 18."""

from __future__ import annotations

import numpy as np


def bestfit(cap: np.ndarray, used: np.ndarray) -> np.ndarray:
    """cap, used: (..., 2) float64 (cpu MHz, memory MB) -> (...) in [0, 1]."""
    with np.errstate(divide="ignore", invalid="ignore"):
        free = 1.0 - used / cap
    total = np.power(10.0, free).sum(axis=-1)
    return np.clip(20.0 - total, 0.0, 18.0) / 18.0


def mean_fitness(cap: np.ndarray, used_final: np.ndarray,
                 counts: np.ndarray) -> float:
    """Order-independent packing quality (bench.packing_score_store's
    formula): every placement of the cell scores the fitness of its
    node's FINAL usage; the mean over the cell's placements."""
    n = float(counts.sum())
    if n == 0:
        return 0.0
    return float((counts * bestfit(cap, used_final)).sum() / n)
