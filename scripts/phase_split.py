#!/usr/bin/env python3
"""One traced run of a benchmark cell, split by the phase spans of the
two critical sections (PERF.md section 5).

    python3 scripts/phase_split.py --workload grid.spread.300 --seed 7
    python3 scripts/phase_split.py --workload c2m.backlog --seed 7 --admit

Runs the cell through the benchmark's own harness (`--trace 1`), keeps
the TRACER records the harness handed its readers and the profiler's
`.xplane.pb`, and prints, after the harness's result line, one
`[phase_split]` JSON line:

- per span name: count, median, total and longest (ms);
- placer.stage, the packing and shipping that left the lock: median,
  total, the share of it that ran while another thread was inside
  placer.locked, and nomad.placer.staged_solves beside the count of
  placer.locked spans over the whole process;
- per evaluation: placer.lock_wait + placer.locked over worker.solve,
  and the six children over placer.locked (the least share seen);
- placer.locked wall against its `cpu_s`; plan.commit_round the same;
- the hand-over: the gaps between one holder's placer.locked and the
  next one's, over all threads;
- the launch round trip: placer.device_wait less the device time of
  `jit_solve_task_group_fused` a launch, both from the trace file;
- whether every launch of that program in the device trace lies inside
  exactly one placer.ship ... placer.device_wait stretch of that file;
- the longest commit rounds, each with its store.lock_wait / apply /
  publish, its listeners by `fn`, and its off-CPU time.

`--admit` runs an unlisted cell (`c2m.backlog`, files kept under
benchmark/) in a copy of the benchmark that lists it, as
benchmark/tests/test_data_driven.py does. Nothing here is read by the
benchmark; the numbers go to PERF.md by hand.
"""

from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

T_START = time.time()
ROOT = Path(__file__).resolve().parents[1]
LOCKED_CHILDREN = ("placer.gather", "placer.pack", "placer.ship",
                   "placer.device_wait", "placer.fetch", "placer.register")
PROGRAM = "jit_solve_task_group_fused"
# record layout of nomad_tpu.obs.trace
NAME, PARENT, ID, T0, T1, THREAD, ARGS = 0, 2, 3, 4, 5, 6, 7

ADMIT = {
    "c2m.backlog": {
        "config": "c2m-10k", "traffic": "backlog",
        "metrics": [("solver.wait_ms", "ms", "lower", "program_span"),
                    ("solver.evals_per_launch", "evals", "higher",
                     "program_counter"),
                    ("solver.resyncs", "count", "lower", "program_counter"),
                    ("solve_bulk_multi_ms", "ms", "lower", "device_trace"),
                    ("solve_bulk_multi_roofline", "%", "higher",
                     "device_trace")]}}


def admit(cell: str) -> Path:
    """A copy of the benchmark that lists `cell` -> its root."""
    root = ROOT / ".bench_check" / cell
    shutil.rmtree(root, ignore_errors=True)
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__",
                                                  "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = ADMIT[cell]
    config = json.loads(
        (ROOT / f"benchmark/configs/{spec['config']}.json").read_text())
    bench["configs"].append({
        "name": spec["config"], "source": config["source"],
        "file": f"benchmark/configs/{spec['config']}.json",
        "reduced": config["reduced"], "why": "admitted in a copy"})
    bench["workloads"].append({
        "name": cell, "config": spec["config"], "traffic": spec["traffic"],
        "chips": 1, "why": "admitted in a copy"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    for name, unit, better, source in spec["metrics"]:
        layer = json.loads((ROOT / "benchmark/layer_metrics"
                            / f"{name}.json").read_text())["layer"]
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "allocs_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def ms(x: float) -> float:
    return round(1e3 * x, 3)


def dur(r) -> float:
    return r[T1] - r[T0]


def span_table(records) -> dict:
    by_name: dict = {}
    for r in records:
        by_name.setdefault(r[NAME], []).append(dur(r))
    return {n: {"n": len(d), "median_ms": ms(statistics.median(d)),
                "total_ms": ms(sum(d)), "longest_ms": ms(max(d))}
            for n, d in sorted(by_name.items(), key=lambda kv: -sum(kv[1]))}


def children_of(records) -> dict:
    kids: dict = {}
    for r in records:
        if r[PARENT]:
            kids.setdefault(r[PARENT], []).append(r)
    return kids


def coverage(records, kids) -> dict:
    """The least, and the median, share of a parent its phases cover."""
    solve, locked = [], []
    for r in records:
        if r[NAME] == "worker.solve" and dur(r) > 0:
            solve.append(sum(dur(c) for c in kids.get(r[ID], ())
                             if c[NAME] in ("placer.lock_wait",
                                            "placer.locked")) / dur(r))
        if r[NAME] == "placer.locked" and dur(r) > 0:
            locked.append(sum(dur(c) for c in kids.get(r[ID], ())
                              if c[NAME] in LOCKED_CHILDREN) / dur(r))
    out = {}
    for key, xs in (("wait_plus_locked_of_solve", solve),
                    ("children_of_locked", locked)):
        if xs:
            out[key] = {"n": len(xs), "least": round(min(xs), 4),
                        "median": round(statistics.median(xs), 4)}
    return out


def handover(records) -> dict:
    """Between one holder's placer.locked and the next one's: the time
    the lock is free or being handed over, over all threads."""
    held = sorted((r[T0], r[T1]) for r in records
                  if r[NAME] == "placer.locked")
    gaps = [b[0] - a[1] for a, b in zip(held, held[1:])]
    if not gaps:
        return {}
    return {"n": len(gaps), "median_ms": ms(statistics.median(gaps)),
            "total_ms": ms(sum(gaps)), "longest_ms": ms(max(gaps)),
            "negative": sum(1 for g in gaps if g < 0)}


def stage_overlap(records) -> dict:
    """placer.stage beside the holds of the other threads: how much of
    the staging ran while somebody else held the lock."""
    stages = [r for r in records if r[NAME] == "placer.stage"]
    held = [r for r in records if r[NAME] == "placer.locked"]
    if not stages:
        return {}
    total = sum(dur(r) for r in stages)
    # holds never overlap one another, so the parts add up
    under = sum(max(0.0, min(s[T1], h[T1]) - max(s[T0], h[T0]))
                for s in stages for h in held if h[THREAD] != s[THREAD])
    return {"n": len(stages), "locked_spans": len(held),
            "median_ms": ms(statistics.median(dur(r) for r in stages)),
            "total_ms": ms(total), "longest_ms": ms(max(map(dur, stages))),
            "under_another_hold_ms": ms(under),
            "under_another_hold_pct": round(100 * under / total, 2)
            if total else None,
            "bytes": stages[0][ARGS].get("bytes")}


def staged_against_locked() -> dict:
    """Over the whole process, warm-up included: evaluations staged
    before the lock against holds of the lock (the tracer counts every
    span it closes into the registry)."""
    from nomad_tpu.core.metrics import REGISTRY

    held = REGISTRY.dump().get("nomad.eval.phase.placer.locked") or {}
    return {"nomad.placer.staged_solves":
            REGISTRY.get("nomad.placer.staged_solves"),
            "placer.locked": held.get("count")}


def wall_against_cpu(records, name: str):
    rs = [r for r in records if r[NAME] == name and "cpu_s" in r[ARGS]]
    if not rs:
        return None
    wall = sum(dur(r) for r in rs)
    cpu = sum(r[ARGS]["cpu_s"] for r in rs)
    return {"n": len(rs), "wall_ms": ms(wall), "cpu_ms": ms(cpu),
            "off_cpu_pct": round(100 * (1 - cpu / wall), 2) if wall else None,
            "median_wall_ms": ms(statistics.median(dur(r) for r in rs)),
            "median_cpu_ms": ms(statistics.median(
                r[ARGS]["cpu_s"] for r in rs))}


def longest_rounds(records, kids, top: int = 5) -> list:
    rounds = sorted((r for r in records if r[NAME] == "plan.commit_round"),
                    key=dur, reverse=True)[:top]
    out = []
    for r in rounds:
        row = {"wall_ms": ms(dur(r)), "n": r[ARGS].get("n"),
               "cpu_ms": ms(r[ARGS].get("cpu_s", 0.0))}
        for c in kids.get(r[ID], ()):
            row[c[NAME]] = ms(dur(c))
            if c[NAME] == "store.apply":
                row["rows"] = c[ARGS].get("rows")
                row["blocks"] = c[ARGS].get("blocks")
            if c[NAME] == "store.publish":
                row["events"] = c[ARGS].get("events")
                row["listeners"] = {
                    g[ARGS].get("fn"): ms(dur(g))
                    for g in kids.get(c[ID], ())
                    if g[NAME] == "store.listener"}
        out.append(row)
    return out


def trace_file_check(path: str) -> dict:
    """From the .xplane.pb alone: the annotated placer spans on the host
    plane against the program's launches on the device plane."""
    import warnings

    import jax

    from benchmark.xplane import read_planes

    host: dict = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    name = e.name.split("#")[0]
                    if name.startswith(("placer.", "solver.")):
                        s = e.start_ns * 1e-9
                        host.setdefault((name, line.name), []).append(
                            (s, s + e.duration_ns * 1e-9))
    # a ship ... device_wait stretch: per thread line, each ship paired
    # with the device_wait that follows it
    stretches = []
    for (name, line), ships in host.items():
        if name != "placer.ship":
            continue
        waits = sorted(host.get(("placer.device_wait", line), ()))
        for s0, _ in sorted(ships):
            after = [w for w in waits if w[0] >= s0]
            if after:
                stretches.append((s0, after[0][1]))
    launches = []
    for dev in read_planes(path)["devices"].values():
        launches += [(a, b) for n, a, b in dev["modules"] if n == PROGRAM]
    inside = [sum(1 for s0, s1 in stretches if s0 <= a and b <= s1)
              for a, b in launches]
    waits = [b - a for (n, _), xs in host.items()
             if n == "placer.device_wait" for a, b in xs]
    annotated: dict = {}
    for (name, _), xs in host.items():
        annotated[name] = annotated.get(name, 0) + len(xs)
    # a launch inside no stretch: where it starts, against the end of
    # the last annotation the file holds (a launch under way when the
    # trace stopped has its placer.ship or device_wait still open)
    last = max((b for xs in host.values() for _, b in xs), default=0.0)
    out = {"annotated": dict(sorted(annotated.items())),
           "stretches": len(stretches), "launches": len(launches),
           "launches_inside_exactly_one": sum(1 for k in inside if k == 1),
           "launches_inside_none": [
               {"start_after_last_annotation_ms": ms(a - last),
                "device_ms": ms(b - a)}
               for (a, b), k in zip(launches, inside) if k == 0]}
    if launches and waits:
        kernel = statistics.median(b - a for a, b in launches)
        wait = statistics.median(waits)
        out.update(device_wait_median_ms=ms(wait),
                   kernel_median_ms=ms(kernel),
                   launch_round_trip_ms=ms(wait - kernel))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--admit", action="store_true")
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args()

    root = admit(args.workload) if args.admit else ROOT
    sys.path[0] = str(root)
    if str(ROOT) not in sys.path:
        sys.path.append(str(ROOT))
    from benchmark import harness, observe

    kept: dict = {}
    spans_in_window = observe.spans_in_window

    def keep(t0, t1):
        out = spans_in_window(t0, t1)
        kept["records"] = out["records"]
        return out

    observe.spans_in_window = keep
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", "1"]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    if args.toy:
        argv.append("--toy")
    rc = harness.main(argv, T_START)
    records = kept.get("records")
    if rc or not records:
        return rc or 1
    kids = children_of(records)
    split = {"workload": args.workload, "seed": args.seed,
             "spans": span_table(records),
             "coverage": coverage(records, kids),
             "handover": handover(records),
             "placer.stage": stage_overlap(records),
             "staged_against_locked": staged_against_locked(),
             "placer.locked": wall_against_cpu(records, "placer.locked"),
             "plan.commit_round": wall_against_cpu(records,
                                                   "plan.commit_round"),
             "plan.verify": wall_against_cpu(records, "plan.verify"),
             "longest_rounds": longest_rounds(records, kids)}
    traces = sorted((harness.WORK / args.workload / "trace").glob(
        "plugins/profile/*/*.xplane.pb"))
    if traces:
        split["trace_file"] = trace_file_check(str(traces[-1]))
    print("[phase_split] " + json.dumps(split), flush=True)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"phase_split.{args.workload}.{args.seed}.json").write_text(
        json.dumps(split, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
