"""nomadload open-loop arrival generator (chaos `overload` family).

The defining property of an overload test is that the offered load
does NOT let up when the server slows down: a closed-loop client (next
request after the previous reply) self-throttles in lockstep with the
victim and measures a collapse as "slightly higher latency". This
generator precomputes a seeded Poisson arrival schedule and fires each
request at its scheduled time regardless of how the previous one
fared — requests that find the server slow pile up exactly as a
production rejection storm would, and coordinated omission never
flatters the latency numbers (the schedule, not the replies, decides
when work arrives).

Outcome classification: a ``loadctl.RetryLater`` (or any exception
carrying ``status == 429``) counts as *shed* — the overload plane
doing its job; anything else raised counts as an *error*; a return
counts as *ok* with its service latency recorded.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Dict, List, Optional


def _percentile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    ys = sorted(xs)
    i = min(len(ys) - 1, max(0, int(q * (len(ys) - 1) + 0.5)))
    return ys[i]


def arrival_schedule(rate: float, duration: float,
                     seed: int = 0) -> List[float]:
    """Seeded Poisson arrival offsets (seconds from start) covering
    ``duration`` at ``rate`` requests/s."""
    rng = random.Random(seed)
    out: List[float] = []
    t = 0.0
    while True:
        t += rng.expovariate(rate)
        if t >= duration:
            return out
        out.append(t)


def run_open_loop(submit: Callable[[int], object], rate: float,
                  duration: float, seed: int = 0, workers: int = 8,
                  clock: Callable[[], float] = time.monotonic,
                  sleep: Callable[[float], None] = time.sleep,
                  stop: Optional[threading.Event] = None) -> Dict:
    """Drive ``submit(i)`` on the seeded schedule from a worker pool.

    Workers claim arrivals in schedule order; an arrival whose time
    already passed (every worker busy — the server IS overloaded)
    fires immediately with the backlog intact. Returns aggregate
    counters plus service-latency percentiles over the *ok* requests.
    """
    sched = arrival_schedule(rate, duration, seed=seed)
    lock = threading.Lock()
    state = {"next": 0}
    res = {"sent": 0, "ok": 0, "shed": 0, "errors": 0}
    latencies: List[float] = []
    error_samples: List[str] = []
    start = clock()

    def worker():
        from ..core.loadctl import RetryLater
        while True:
            if stop is not None and stop.is_set():
                return
            with lock:
                i = state["next"]
                if i >= len(sched):
                    return
                state["next"] = i + 1
            wait = sched[i] - (clock() - start)
            if wait > 0:
                sleep(wait)
            t0 = clock()
            try:
                submit(i)
            except RetryLater:
                with lock:
                    res["sent"] += 1
                    res["shed"] += 1
                continue
            except Exception as e:  # noqa: BLE001 — classify, don't die
                with lock:
                    res["sent"] += 1
                    if getattr(e, "status", None) == 429:
                        res["shed"] += 1
                    else:
                        res["errors"] += 1
                        if len(error_samples) < 5:
                            error_samples.append(repr(e))
                continue
            dt = clock() - t0
            with lock:
                res["sent"] += 1
                res["ok"] += 1
                latencies.append(dt)

    threads = [threading.Thread(target=worker, daemon=True,
                                name=f"openloop-{k}")
               for k in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = clock() - start
    res.update({
        "offered": len(sched),
        "duration": wall,
        "goodput": res["ok"] / wall if wall > 0 else 0.0,
        "p50": _percentile(latencies, 0.50),
        "p99": _percentile(latencies, 0.99),
        "error_samples": error_samples,
    })
    return res
