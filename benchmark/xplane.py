"""From a profiler trace (.xplane.pb) to device numbers.

Read with `jax.profiler.ProfileData` alone. What is taken:

- busy: the union of the intervals in which an operation ran on a
  device, per device plane, averaged over the planes; idle share is
  1 - busy / traced window;
- per program: device seconds and launches of each jitted program;
- idle gaps: the complement of busy, each gap cut along the TRACER
  spans of the thread that makes the next launch and totalled by the
  innermost span open there (`label_gap`).

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

import bisect
import json
import warnings
from pathlib import Path
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


# the two clocks are aligned by one annotation, good to well under this
CLOCK_SLACK_S = 0.0005
HOST_SPANS = Path(__file__).resolve().parent / "host_spans"


def span_roles(directory: Path = HOST_SPANS) -> dict:
    """What the benchmark knows of the program's span names, for
    `label_gap`, read from every `host_spans/*.json` (a layer's file
    each, so a new launch site is a new file): `launch`, the spans inside
    which a thread enqueues a launch; `wait`, the spans that are a wait
    and no work; `held_by`, for a wait on a lock the span its holder is
    in."""
    roles = {"launch": set(), "wait": set(), "held_by": {}}
    for path in sorted(directory.glob("*.json")):
        with open(path) as f:
            layer = json.load(f)
        roles["launch"].update(layer.get("launch", ()))
        roles["wait"].update(layer.get("wait", ()))
        roles["held_by"].update(layer.get("held_by", {}))
    return roles


class HostTimeline:
    """The TRACER's spans by thread: which thread launches next, and
    which span a thread was innermost in at a time. `spans` is
    [(name, t0, t1, thread)] on the wall clock, `roles` as `span_roles`
    gives them, `device_starts` the wall times of the trace's program
    launches."""

    def __init__(self, spans: list, roles: dict = None,
                 device_starts=()):
        self.roles = roles = span_roles() if roles is None else roles
        # wall times at which a program started on a device, sorted
        self.device_starts = sorted(device_starts)
        self.by_thread: Dict[str, list] = {}
        self.launches: list = []          # (t0, t1, thread), by t0
        self.holds: Dict[str, list] = {h: [] for h in
                                       roles["held_by"].values()}
        for name, t0, t1, thread in spans:
            self.by_thread.setdefault(thread, []).append((t0, t1, name))
            if name in roles["launch"]:
                self.launches.append((t0, t1, thread))
            if name in self.holds:
                self.holds[name].append((t0, t1, thread))
        self.launches.sort()

    def launcher(self, t: float):
        """The thread that makes the next launch after `t`: of the launch
        spans not yet over at `t` the first to have started, so one that
        is already open there (a second launch in flight) comes before
        the next to start. A span that is open at `t` but has seen a
        program start on the device since it opened (`device_starts`)
        has made its launch already and only outlasted it: it is passed
        over. None when the records hold no later launch."""
        for t0, t1, thread in self.launches:
            if t1 <= t + CLOCK_SLACK_S:
                continue
            if t0 <= t:
                i = bisect.bisect_left(self.device_starts,
                                       t0 - CLOCK_SLACK_S)
                if (i < len(self.device_starts)
                        and self.device_starts[i] <= t + CLOCK_SLACK_S):
                    continue
            return thread
        return None

    def inside(self, thread: str, a: float, b: float) -> list:
        """[(name | None, x, y)]: [a, b] cut where the thread's innermost
        open span changes. Spans of one thread nest, so the innermost of
        those open at a time is the one that started last."""
        rows = [r for r in self.by_thread.get(thread, ())
                if r[0] < b and r[1] > a]
        cuts = sorted({a, b} | {t for r in rows for t in r[:2] if a < t < b})
        out = []
        for x, y in zip(cuts, cuts[1:]):
            mid = 0.5 * (x + y)
            open_ = [r for r in rows if r[0] <= mid < r[1]]
            name = (max(open_, key=lambda r: (r[0], -r[1]))[2]
                    if open_ else None)
            if out and out[-1][0] == name:
                out[-1] = (name, out[-1][1], y)
            else:
                out.append((name, x, y))
        return out


def label_gap(gap: Tuple[float, float], host: HostTimeline) -> Dict[str, float]:
    """-> {label: seconds}, summing to the gap: what stood between the
    device and its next launch. The gap is cut along the thread that
    makes that launch (`HostTimeline.launcher` at the gap's start: the
    launch span opens before the device starts) and each
    piece takes the name of the innermost span open on that thread, so a
    span that 24 waiting workers hold open explains nothing by their
    number. A pure wait reads `wait.<span>`; while the launcher waits for
    a lock, the piece is cut along the lock's holder instead, and what is
    left of it (nobody holds the lock: the hand-over) stays the wait."""
    a, b = gap
    out: Dict[str, float] = {}

    def add(name, seconds: float) -> None:
        label = ("no_span" if name is None else
                 f"wait.{name}" if name in host.roles["wait"] else name)
        out[label] = out.get(label, 0.0) + seconds

    thread = host.launcher(a)
    if thread is None:
        return {"no_launch_follows": b - a}
    for name, x, y in host.inside(thread, a, b):
        left = y - x
        for h0, h1, holder in host.holds.get(
                host.roles["held_by"].get(name), ()):
            if holder == thread or h0 >= y or h1 <= x:
                continue
            for inner, u, v in host.inside(holder, max(x, h0), min(y, h1)):
                add(inner, v - u)
                left -= v - u
        if left > 0:
            add(name, left)
    return out


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


def _top_gaps(totals: Dict[str, float], n_dev: int, top: int) -> list:
    """Seconds a device by label, longest first, at most `top` entries
    that still sum to the idle time: what does not fit is `other`."""
    rows = sorted(([n, s / n_dev] for n, s in totals.items()),
                  key=lambda r: -r[1])
    if len(rows) > top:
        rows[top - 1:] = [["other", sum(s for _, s in rows[top - 1:])]]
    return rows


def reduce_trace(path: str, window: Tuple[float, float], spans: list,
                 top: int = 10) -> dict:
    """`window` = (wall t0, wall t1) of the traced stretch; `spans` =
    [(name, wall t0, wall t1, thread)] from the TRACER. -> busy_s, window_s,
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
    host = HostTimeline(spans, device_starts=(
        [] if offset is None else
        [a + offset for dev in devices.values()
         for _, a, _ in dev["modules"]]))
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
                pieces = label_gap((g[0] + offset, g[1] + offset), host)
                for label, seconds in pieces.items():
                    gap_totals[label] = gap_totals.get(label, 0.0) + seconds
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
        "idle_gaps": _top_gaps(gap_totals, n_dev, top),
        "clock_aligned": clock is not None,
    }
