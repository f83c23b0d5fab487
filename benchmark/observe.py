"""What the benchmark reads from the program: compile events, errors off
the main thread, TRACER spans and the subsystems' counters.

Nothing here times an end-to-end metric; those come from the client's
clock (benchmark/loadgen.py). This is the per-layer side: span medians
and counter deltas over the window, handed to the layer-metric readers
as one `observations` dict.
"""

from __future__ import annotations

import logging
import statistics
import threading
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _ErrorLog(logging.Handler):
    def __init__(self, sink: list):
        super().__init__(level=logging.ERROR)
        self.sink = sink

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        exc = record.exc_info[1] if record.exc_info else None
        if exc is not None:
            msg += f" [{type(exc).__name__}: {exc}]"
        self.sink.append((time.time(), f"log {record.name}: {msg}"))


class Watch:
    """Uncaught thread exceptions, ERROR log records, and every XLA
    compile with its program name, seconds, wall time and whether the
    persistent cache served it (copy of chip_smoke.Watch, plus the time
    stamp that places a compile inside or outside the window)."""

    def __init__(self):
        import jax

        self.errors: list = []        # (wall time, text)
        self.compiles: list = []      # (name, seconds, cache_hit, t_end)
        self._tls = threading.local()
        self._prev_hook = threading.excepthook
        threading.excepthook = self._on_thread_exc
        self._handler = _ErrorLog(self.errors)
        logging.getLogger().addHandler(self._handler)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_thread_exc(self, args) -> None:
        name = args.thread.name if args.thread else "?"
        self.errors.append((time.time(), f"thread {name}: "
                            f"{args.exc_type.__name__}: {args.exc_value}"))
        self._prev_hook(args)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_HIT_EVENT:
            self._tls.hit = True

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event != COMPILE_EVENT:
            return
        hit, self._tls.hit = getattr(self._tls, "hit", False), False
        name = str(kwargs.get("fun_name", "?"))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        self.compiles.append((name, duration, hit, time.time()))

    def close(self) -> None:
        threading.excepthook = self._prev_hook
        logging.getLogger().removeHandler(self._handler)

    def between(self, t0: float, t1: float) -> list:
        return [c for c in self.compiles if t0 <= c[3] <= t1]


def counters(server) -> dict:
    """One reading of every counter a layer metric may name, by path."""
    from nomad_tpu.core.metrics import REGISTRY
    from nomad_tpu.tensor import incremental
    from nomad_tpu.tensor.solver import get_service

    out = {"solver": dict(get_service().stats),
           "applier": dict(server.plan_applier.stats),
           "feed": dict(incremental.GLOBAL.stats())}
    # flat names -> numbers (histogram families are dicts: left out)
    out["registry"] = {k: v for k, v in REGISTRY.dump().items()
                       if isinstance(v, (int, float))}
    return out


def delta(after: dict, before: dict) -> dict:
    out = {}
    for group, vals in after.items():
        b = before.get(group, {})
        out[group] = {k: v - b.get(k, 0) for k, v in vals.items()
                      if isinstance(v, (int, float))
                      and isinstance(b.get(k, 0), (int, float))}
    return out


def spans_in_window(t0: float, t1: float) -> dict:
    """TRACER spans that ENDED inside [t0, t1] -> {"records": [...],
    "durations": {name: [s...]}, "self": {name: [s...]}, "rings_full":
    n}. Self time is a span's duration less the part its direct children
    (same thread, parent id) cover. A full ring has overwritten its
    oldest records: the count of full rings is reported, since the
    tracer itself keeps no drop counter."""
    from nomad_tpu.obs import TRACER
    from nomad_tpu.obs.trace import (R_ID, R_NAME, R_PARENT, R_T0, R_T1)

    with TRACER._reg_lock:
        rings = list(TRACER._rings.values())
    full = sum(1 for r in rings if len(r.buf) >= r.cap)
    recs = [r for r in TRACER.spans() if t0 <= r[R_T1] <= t1]
    child_time: dict = {}
    for r in recs:
        if r[R_PARENT]:
            child_time[r[R_PARENT]] = (child_time.get(r[R_PARENT], 0.0)
                                       + (r[R_T1] - r[R_T0]))
    durations: dict = {}
    selfs: dict = {}
    for r in recs:
        d = r[R_T1] - r[R_T0]
        durations.setdefault(r[R_NAME], []).append(d)
        selfs.setdefault(r[R_NAME], []).append(
            max(0.0, d - child_time.get(r[R_ID], 0.0)))
    return {"records": recs, "durations": durations, "self": selfs,
            "rings_full": full}


def spans_overlapping(t0: float, t1: float) -> list:
    """[(name, t0, t1, thread)] of every TRACER span that overlaps
    [t0, t1]: what each thread of the host was doing, for labelling the
    device's idle gaps."""
    from nomad_tpu.obs import TRACER
    from nomad_tpu.obs.trace import R_NAME, R_T0, R_T1, R_THREAD

    return [(r[R_NAME], r[R_T0], r[R_T1], r[R_THREAD])
            for r in TRACER.spans() if r[R_T1] > t0 and r[R_T0] < t1]


def stat(values: list, which: str) -> float:
    if not values:
        raise LookupError("no samples")
    if which == "median":
        return statistics.median(values)
    if which == "mean":
        return statistics.fmean(values)
    if which == "sum":
        return float(sum(values))
    if which == "count":
        return float(len(values))
    if which.startswith("p"):
        xs = sorted(values)
        q = float(which[1:]) / 100.0
        return xs[min(len(xs) - 1, int(q * len(xs)))]
    raise ValueError(f"unknown statistic {which!r}")
