"""The harness: BENCHMARK.json + data files -> one run of one cell.

Driven by data. A cell names a configuration (`configs/<name>.json`) and
a traffic mix (`traffic/<mix>.json`); the configuration names its deploy
kind (`deploy/<kind>.py`), the traffic its generator kind
(`generators/<kind>.py`); per-layer metrics are `layer_metrics/<name>.*`.
All are found by name through importlib, so a new cell made of existing
kinds, or a new per-layer metric over an existing span, is new files
plus entries in BENCHMARK.json.

Phases of a run: set-up (agent, fleet, warm-up of the cell's own
shapes, backlog; all of it is `setup_s`), the window (`--seconds`; with
`--trace 1` under jax.profiler until the clock stops, its first seconds
at most), the check (`correct`, outside the window), teardown, the
result line. Where the configuration asks for the backlog in several
rounds (`window.rounds`, generators/backlog.py) the window is the
rounds' windows together: the end-to-end rate is taken over all of
them, what lies between two of them is set-up and counts in `setup_s`,
every round's end state is checked before the next round purges it, and
the per-layer observations (spans, counters, the trace) are the first
window's. A deploy kind whose `quiesce()` ends the deployment
(`three_servers`) runs one round.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import logging
import os
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
WORK = HERE / ".work"          # plans and traces of the last run (ignored)
TRACE_RING = "262144"


class Context:
    """What one run's pieces share."""

    def __init__(self, cell, config, traffic, seed, seconds, toy, workdir):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.toy = seed, seconds, toy
        self.workdir = workdir
        self.deployment = None

    def note(self, topic: str, **obs) -> None:
        """One line per observation on stdout (before the result line)."""
        print(f"[{topic}] " + " ".join(
            f"{k}={json.dumps(v, default=str)}" for k, v in obs.items()),
            flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, toy: bool) -> tuple:
    """-> (benchmark, cell, config, traffic) with toy overrides applied."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(ROOT / files[cell["config"]])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    if toy:
        traffic = {**traffic, **traffic.get("toy", {})}
    return bench, cell, config, traffic


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Tracing:
    """The profiler around the first `seconds` of the window."""

    def __init__(self, ctx, seconds: float):
        self.ctx, self.seconds = ctx, seconds
        self.dir = ctx.workdir / "trace"
        shutil.rmtree(self.dir, ignore_errors=True)
        self.t0 = self.t1 = None
        self._timer = None
        self._lock = threading.Lock()

    def start(self) -> None:
        import jax

        from benchmark.xplane import CLOCK_NAME

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the TRACER's spans label the host
        opts.host_tracer_level = 1        # keeps TraceAnnotations
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(CLOCK_NAME, t=repr(time.time())):
            pass
        self.t0 = time.time()
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        import jax

        with self._lock:
            if self.t1 is not None or self.t0 is None:
                return
            self.t1 = time.time()
            jax.profiler.stop_trace()

    def finish(self):
        if self._timer is not None:
            self._timer.cancel()
        self.stop()
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        return str(files[-1]) if files else None


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--toy", action="store_true",
                    help="rehearsal sizes, any backend; never a measurement")
    args = ap.parse_args(argv)

    missing = [m for m in ("nomad_tpu",)
               if importlib.util.find_spec(m) is None]
    if missing or not (ROOT / "BENCHMARK.json").exists():
        print("benchmark: the program (nomad_tpu) is not next to the "
              "benchmark; nothing was run", file=sys.stderr)
        return 2
    bench, cell, config, traffic = load_cell(args.workload, args.toy)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    if args.trace:
        os.environ.setdefault("NOMAD_TPU_TRACE_RING", TRACE_RING)

    import jax

    devs = jax.devices()
    if not args.toy and (devs[0].platform != "tpu"
                         or len(devs) < int(cell["chips"])):
        print(f"benchmark: {cell['name']} needs {cell['chips']} TPU chip(s), "
              f"JAX resolved {len(devs)} x {devs[0].platform} "
              f"({devs[0].device_kind}); nothing was run", file=sys.stderr)
        return 3
    logging.basicConfig(level=logging.WARNING)

    from benchmark import check, layers, observe, xplane

    ctx = Context(cell, config, traffic, args.seed, seconds, args.toy,
                  WORK / cell["name"])
    watch = observe.Watch()
    deploy_mod = importlib.import_module(
        f"benchmark.deploy.{config['deploy']}")
    gen_mod = importlib.import_module(
        f"benchmark.generators.{traffic['generator']}")
    dep = ctx.deployment = deploy_mod.deploy(config, args.seed, args.toy)
    gen = None
    result = None
    stopped = False
    try:
        dep.start()
        # every program this run compiles goes to the persistent cache,
        # whatever it cost: the program's own threshold (0.5 s) leaves the
        # scatter buckets and scan remainders to be compiled by every run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        ctx.note("env", workload=cell["name"], seed=args.seed,
                 seconds=seconds, trace=args.trace, toy=args.toy,
                 platform=devs[0].platform, device_kind=devs[0].device_kind,
                 devices=len(devs), jax=jax.__version__, nodes=dep.nodes)
        from benchmark.traffic import warm_specs

        warm = traffic.get("warm", {})
        from nomad_tpu.tensor.placer import TPUPlacer

        # the program's own cut-over: at or under it the host scores the
        # placements and nothing compiles, so no warm job is needed
        traffic.setdefault("warm", {}).setdefault(
            "host_cutover", int(TPUPlacer.HOST_CUTOVER))
        ctx.note("warm", **dep.warm(
            warm_specs(traffic, f"warm-{args.seed}"),
            bool(warm.get("scatter_buckets"))))
        gen = gen_mod.Generator(ctx)
        gen.prepare()
        attribute = check.spread_attribute(traffic)
        rules = traffic.get("check", {})
        tracing = (Tracing(ctx, float(traffic.get("trace_seconds", 4)))
                   if args.trace else None)
        marks: dict = {"compiles": []}
        states: list = []

        def on_open(k: int) -> None:
            marks["used0"] = check.cluster_arrays(
                dep.server.store.snapshot(), attribute)["used"]
            if k == 0 and tracing is not None:
                tracing.start()
            marks["open"] = observe.counters(dep.server)
            if k == 0:
                marks["c0"] = marks["open"]
                marks["setup_s"] = time.time() - t_start

        def on_clock_stop(k: int, t0: float, t1: float) -> None:
            if tracing is not None:
                tracing.stop()      # the trace never outlasts the clock
            marks["stop"] = observe.counters(dep.server)
            if k == 0:
                marks["c1"] = marks["stop"]
            marks["compiles"] += watch.between(t0, t1)

        def on_round_end(k: int, rnd: dict) -> None:
            # the round's end state, read from a store nobody writes to;
            # the reference runs on it after the deployment is stopped
            marks["quiesced"] = dep.quiesce()
            solver = observe.delta(marks["stop"], marks["open"])["solver"]
            states.append(check.end_state(
                dep.server, rnd["specs"], rnd["complete"], marks["used0"],
                rules, args.seed, solver, [], attribute))

        ctx.note("setup", **{k: round(v, 3) for k, v in dep.timings.items()},
                 compiles=len(watch.compiles),
                 compile_s=round(sum(c[1] for c in watch.compiles), 3),
                 cache_hits=sum(1 for c in watch.compiles if c[2]))
        out = gen.run(seconds, on_open, on_clock_stop, on_round_end)
        # what lies between two rounds is set-up
        setup_s = marks["setup_s"] + out["rearm_s"]
        ctx.note("setup", setup_s=round(setup_s, 3),
                 to_first_window_s=round(marks["setup_s"], 3))

        # -- per-layer observations (read after the clock stopped) ------
        window = (out["t0"], out["t1"])
        in_window = marks["compiles"]
        ctx.note("compiles_in_window", n=len(in_window),
                 cache_hits=sum(1 for c in in_window if c[2]),
                 seconds=round(sum(c[1] for c in in_window), 4),
                 programs=sorted({c[0] for c in in_window}))
        delta = observe.delta(marks["c1"], marks["c0"])
        delta["window"] = {"compiles": len(in_window)}
        obs = {"counters": delta, "client": out["client"], "profile": {},
               "spans": {"durations": {}, "self": {}},
               "run": {"nodes": dep.nodes, "specs": gen.specs,
                       "spread_values": int(config["node_mix"]["racks"]),
                       "device_kind": (None if args.toy
                                       else devs[0].device_kind)}}
        breakdown = None
        device_extra = {}
        if args.trace:
            spans = observe.spans_in_window(*window)
            obs["spans"] = spans
            # per span name [count, total s, longest s], largest total
            # first: where the host's time went, beside the medians
            totals = sorted(((n, [len(d), round(sum(d), 3), round(max(d), 3)])
                             for n, d in spans["durations"].items()),
                            key=lambda kv: -kv[1][1])
            ctx.note("spans", records=len(spans["records"]),
                     rings_full=spans["rings_full"], totals=dict(totals))
            path = tracing.finish()
            if path is None:
                raise RuntimeError("the profiler wrote no trace")
            t_lo, t_hi = tracing.t0, tracing.t1
            # a second of records past the trace: the launch that ends
            # its last idle gap may open after it
            reduced = obs["profile"] = xplane.reduce_trace(
                path, (t_lo, t_hi),
                observe.spans_overlapping(t_lo, t_hi + 1.0))
            ctx.note("profile", trace=path,
                     trace_bytes=os.path.getsize(path),
                     busy_s=reduced["busy_s"], window_s=reduced["window_s"],
                     idle_pct=reduced["idle_pct"],
                     clock_aligned=reduced["clock_aligned"],
                     programs={k: [round(v["seconds"], 6), v["launches"]]
                               for k, v in reduced["programs"].items()})
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            device_extra = {"busy_s": reduced["busy_s"],
                            "window_s": reduced["window_s"]}

        # -- correct (outside the window) --------------------------------
        t_check = time.perf_counter()

        def in_a_window(t: float) -> bool:
            return any(t0 <= t <= t1 for t0, t1 in out["windows"])

        # an error inside a window always counts; outside them (set-up,
        # the check's own pause, teardown) the configuration may list a
        # pattern it has seen there and explains
        tolerated = config.get("tolerated_errors_outside_window", {})
        fatal = [e for t, e in watch.errors
                 if in_a_window(t) or not any(pat in e for pat in tolerated)]
        ctx.note("errors", fatal=len(fatal), in_window=sum(
            in_a_window(t) for t, _ in watch.errors),
            tolerated={pat: sum(pat in e for _, e in watch.errors)
                       for pat in tolerated})
        states[-1]["errors"] = fatal
        beats = dict(dep.swarm.stats)
        stats = devs[0].memory_stats() or {}
        gen.close()
        dep.stop()
        stopped = True
        memo: dict = {}
        verdict = check.judge_rounds(
            [check.add_reference(state, memo) for state in states], rules)
        failed = sorted(set(out["failed_ids"]) | set(verdict["failed_jobs"]))
        ctx.note("check", correct=verdict["correct"],
                 reasons=verdict["reasons"],
                 complete_jobs=[len(st["jobs"]) for st in states],
                 fitness=[round(st["fitness"], 5) for st in states],
                 reference_fitness=[round(st["reference_fitness"], 5)
                                    for st in states],
                 reference_unplaced=[st["reference_unplaced"]
                                     for st in states],
                 spread_jobs=[len(st["spread"]) for st in states],
                 spread_worst=max((max(p) - min(p) for st in states
                                   for p in st["spread"].values()),
                                  default=None),
                 heartbeats=beats["heartbeats"],
                 hb_failures=beats["hb_failures"],
                 quiesced=marks["quiesced"], took=states[0]["took"],
                 references_run=len(memo),
                 check_s=round(time.perf_counter() - t_check, 3))
        ctx.note("counters", solver={k: v for k, v in delta["solver"].items()
                                     if v}, applier=delta["applier"],
                 feed={k: v for k, v in delta["feed"].items() if v})

        # -- the result line ---------------------------------------------
        if args.trace:
            names = [m["name"] for m in metrics_of(bench, "per_layer",
                                                   cell["name"])]
            metrics = layers.read_all(names, obs)
        else:
            units = {m["name"]: m["unit"]
                     for m in metrics_of(bench, "end_to_end", cell["name"])}
            values = dict(out["end_to_end"], setup_s=setup_s)
            metrics = {n: {"value": float(values[n]), "unit": u}
                       for n, u in units.items() if n in values}
        result = {
            "correct": bool(verdict["correct"]),
            "attempted": int(out["attempted"]), "failed": len(failed),
            "metrics": metrics,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs),
                       "memory_peak_bytes": int(
                           stats.get("peak_bytes_in_use", 0)),
                       **device_extra},
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        # each number `correct` compared, beside its limit: last here
        # and, below, the last lines of standard error
        result["checked"] = verdict["compared"]
    except Exception:
        traceback.print_exc()
    finally:
        if gen is not None:
            gen.close()
        if not stopped:
            try:
                dep.stop()
            except Exception:
                traceback.print_exc()
        watch.close()
    if result is None:
        return 1
    for name, (value, limit) in result["checked"].items():
        print(f"checked {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
