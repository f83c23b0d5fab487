"""From a profiler trace (.xplane.pb) to device numbers.

Read with `jax.profiler.ProfileData` alone. What is taken:

- busy: the union of the intervals in which an operation ran on a
  device, per device plane, averaged over the planes; idle share is
  1 - busy / traced window;
- per program: device seconds and launches of each jitted program;
- idle gaps: the complement of busy, each gap labelled by the TRACER
  span the host spent most of it in, totalled by label.

On a TPU the device planes are named `/device:TPU:<n>`; their `XLA Ops`
line holds one event per executed HLO op and their `XLA Modules` line
one per program launch. On the CPU (rehearsal and tests only) there is
no device plane: the PjRt client's thread lines in `/host:CPU` carry the
op events, tagged with their `hlo_module`.

The two clocks: the benchmark drops one `TraceAnnotation(CLOCK_NAME,
t=time.time())`; its start in trace nanoseconds and its `t` place every
trace event on the host's wall clock, where the TRACER's spans live.
"""

from __future__ import annotations

import warnings
from typing import Dict, List, Tuple

CLOCK_NAME = "bench.clock"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: List[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of merged `busy` inside [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if b <= lo or a >= hi:
            continue
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def label_gap(gap: Tuple[float, float], spans: list) -> str:
    """The span name whose records overlap the gap the longest, summed
    over threads. `spans` is [(name, t0, t1)] on the same clock."""
    a, b = gap
    best: Dict[str, float] = {}
    for name, t0, t1 in spans:
        lap = min(b, t1) - max(a, t0)
        if lap > 0:
            best[name] = best.get(name, 0.0) + lap
    if not best:
        return "no span"
    name = max(best, key=best.get)
    # a span that covers under a quarter of the gap does not explain it
    return name if best[name] >= 0.25 * (b - a) else "no span"


def _strip(name: str) -> str:
    return name.split("(")[0]


def read_planes(path: str) -> dict:
    """-> {"devices": {plane: {"ops": [(s, e)], "modules": [(name, s,
    e)]}}, "clock": (trace_s, wall_s) | None}; times in seconds on the
    trace's own clock."""
    import jax

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = jax.profiler.ProfileData.from_file(path)
        devices: dict = {}
        clock = None
        host_ops: list = []
        host_modules: dict = {}
        for plane in data.planes:
            is_device = plane.name.startswith("/device:") and \
                "CUSTOM" not in plane.name
            for line in plane.lines:
                if is_device and line.name in (OPS_LINE, MODULES_LINE):
                    dev = devices.setdefault(plane.name,
                                             {"ops": [], "modules": []})
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        t = (s, s + e.duration_ns * 1e-9)
                        if line.name == OPS_LINE:
                            dev["ops"].append(t)
                        else:
                            dev["modules"].append((_strip(e.name),) + t)
                    continue
                if plane.name != "/host:CPU":
                    continue
                for e in line.events:
                    if e.name == CLOCK_NAME:
                        stats = dict(e.stats)
                        if "t" in stats:
                            clock = (e.start_ns * 1e-9, float(stats["t"]))
                    elif not devices and e.duration_ns > 0:
                        stats = dict(e.stats)
                        mod = stats.get("hlo_module")
                        if mod is not None:
                            s = e.start_ns * 1e-9
                            t = (s, s + e.duration_ns * 1e-9)
                            host_ops.append(t)
                            key = (str(mod), stats.get("run_id"))
                            lo, hi = host_modules.get(key, t)
                            host_modules[key] = (min(lo, t[0]), max(hi, t[1]))
    if not devices and host_ops:
        # CPU rehearsal: one launch = one (module, run id) pair
        devices["/host:CPU"] = {
            "ops": host_ops,
            "modules": [(k[0],) + v for k, v in host_modules.items()]}
    return {"devices": devices, "clock": clock}


def reduce_trace(path: str, window: Tuple[float, float], spans: list,
                 top: int = 10) -> dict:
    """`window` = (wall t0, wall t1) of the traced stretch; `spans` =
    [(name, wall t0, wall t1)] from the TRACER. -> busy_s, window_s,
    idle_pct, programs {name: {"seconds", "launches"}}, device_ops and
    idle_gaps (the contract's `breakdown`)."""
    planes = read_planes(path)
    devices = planes["devices"]
    if not devices:
        raise RuntimeError(f"no device operation in the trace {path}")
    clock = planes["clock"]
    window_s = window[1] - window[0]
    if clock is not None:
        offset = clock[1] - clock[0]          # trace seconds -> wall
        lo, hi = window[0] - offset, window[1] - offset
    else:
        all_ops = [t for d in devices.values() for t in d["ops"]]
        lo = min(a for a, _ in all_ops)
        hi, offset = lo + window_s, None
    busy_each, programs = [], {}
    gap_totals: Dict[str, float] = {}
    for dev in devices.values():
        ops = [(max(a, lo), min(b, hi)) for a, b in dev["ops"]
               if b > lo and a < hi]
        busy = merge(ops or [(max(a, lo), min(b, hi))
                             for _, a, b in dev["modules"]
                             if b > lo and a < hi])
        busy_each.append(sum(b - a for a, b in busy))
        for name, a, b in dev["modules"]:
            if b <= lo or a >= hi:
                continue
            row = programs.setdefault(name, {"seconds": 0.0, "launches": 0})
            row["seconds"] += b - a
            row["launches"] += 1
        if offset is not None:
            for g in gaps(busy, lo, hi):
                wall_gap = (g[0] + offset, g[1] + offset)
                label = label_gap(wall_gap, spans)
                gap_totals[label] = gap_totals.get(label, 0.0) + g[1] - g[0]
    n_dev = len(devices)
    for row in programs.values():
        row["seconds"] /= n_dev
        row["launches"] = row["launches"] / n_dev
    busy_s = sum(busy_each) / n_dev
    by_time = sorted(programs.items(), key=lambda kv: -kv[1]["seconds"])
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s) if window_s else None,
        "programs": programs,
        "device_ops": [[n, r["seconds"]] for n, r in by_time[:top]],
        "idle_gaps": [[n, s] for n, s in sorted(
            gap_totals.items(), key=lambda kv: -kv[1])[:top]],
        "clock_aligned": clock is not None,
    }
