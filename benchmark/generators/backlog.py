"""Generator kind `backlog`: the whole load is registered over HTTP into
a paused broker during set-up; opening the window resumes the broker.

End-to-end metric: `allocs_per_s` = allocations of jobs whose evaluation
chain the client saw complete / seconds since the broker was resumed,
the clock stopping when jobs holding `stop_share` of the backlog's
allocations are complete, or at --seconds. A straggler on a follow-up
timer then costs its own allocations, not a tripled time; the time to
100% is printed beside it.

A configuration may ask for the backlog more than once (`window.rounds`
in its file): a drain of a second or two is too short a reading of a
host-bound path. Every round is the same backlog under new job ids on
the cluster as the first round found it: the round before is purged and
drained, the broker paused, the jobs registered, a new client
subscribed, and the broker resumed. The metric is then all rounds'
allocations / all rounds' seconds; what lies between two rounds is
handed to the harness as `rearm_s`, which counts it as set-up. --seconds
bounds the rounds' seconds together."""

from __future__ import annotations

import time

from benchmark import traffic as traffic_mod
from benchmark.generators.child import Child
from benchmark.jobs import build_job

SHARES = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


def rounds_of(config: dict, toy: bool) -> int:
    window = dict(config.get("window", {}))
    if toy:
        window.update(config.get("toy", {}).get("window", {}))
    return max(1, int(window.get("rounds", 1)))


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        self.rounds = rounds_of(ctx.config, ctx.toy)
        self.share = float(ctx.traffic.get("stop_share", 0.9))
        self.specs = self.specs_of(0)
        self.child = None

    def specs_of(self, k: int) -> list:
        """Round k's jobs: the seed's backlog, under ids of the round."""
        t, ctx = self.ctx.traffic, self.ctx
        prefix = f"{ctx.cell['name']}-{ctx.seed}" + (f"-r{k}" if k else "")
        return traffic_mod.job_specs(t, ctx.seed, int(t["jobs"]), prefix)

    def prepare(self) -> None:
        self._load(self.specs)

    def _load(self, specs: list) -> None:
        dep, t = self.ctx.deployment, self.ctx.traffic
        jobs = [build_job(s) for s in specs]
        t0 = time.perf_counter()
        dep.pause_broker(True)
        sheds = dep.submit(jobs, threads=int(t.get("submit_threads", 8)))
        self.ctx.note("backlog", jobs=len(jobs), sheds=sheds,
                      allocs=sum(s["count"] for s in specs),
                      submit_s=round(time.perf_counter() - t0, 3))
        self.child = Child(
            {"address": dep.address, "stop_share": self.share,
             "jobs": [{"id": s["id"], "count": s["count"]}
                      for s in specs]}, self.ctx.workdir)
        if self.child.wait_for("ready", 60.0) is None:
            raise RuntimeError("load generator not ready")

    def _rearm(self, done: list, specs: list) -> None:
        """Between two rounds: the last round's jobs leave the cluster
        (the same purge and drain the warm-up's jobs get), then the next
        round's are loaded as the first were."""
        dep = self.ctx.deployment
        ids = [s["id"] for s in done]
        dep.pause_broker(False)
        for job_id in ids:
            dep.api.deregister_job(job_id, purge=True)
        dep.drain(ids, timeout=60.0)
        # ... and the store: `nomad system gc` collects their terminal
        # rows, which would otherwise pile up round by round
        self.ctx.note("gc", **{k: v for k, v in dep.api.system_gc().items()
                               if isinstance(v, (int, float))})
        self._load(specs)

    def _round(self, k: int, specs: list, seconds: float,
               on_open, on_clock_stop) -> dict:
        dep = self.ctx.deployment
        on_open(k)
        t0 = time.time()
        dep.pause_broker(False)
        share = self.child.wait_for("share", seconds)
        t_stop = share["t"] if share else t0 + seconds
        on_clock_stop(k, t0, t_stop)
        # the time to 100% is printed, not judged: wait for it a little
        # past the clock's stop, never past the window
        tail = float(self.ctx.traffic.get("tail_wait_seconds", 8))
        done_all = self.child.wait_for("all", max(0.0, min(
            seconds - (time.time() - t0), t_stop + tail - time.time())))
        report = self.child.finish()
        count = {s["id"]: s["count"] for s in specs}
        done_at = report["done_at"]
        allocs = sum(count[j] for j, t in done_at.items() if t <= t_stop)
        evals = [s for rows in report["evals"].values() for s in rows]
        window_s = t_stop - t0
        # [seconds, allocations] when each share of the backlog was complete:
        # printed, not judged (what a lower or higher stop share reads)
        total, acc, reached = sum(count.values()), 0, {}
        for j, t in sorted(done_at.items(), key=lambda kv: kv[1]):
            acc += count[j]
            for q in SHARES:
                if q not in reached and acc >= q * total:
                    reached[q] = [round(t - t0, 3), acc]
        self.ctx.note("timeline", **{f"at_{int(100 * q)}pct":
                                     reached.get(q) for q in SHARES})
        self.ctx.note(
            "window", round=k, seconds=round(window_s, 4), allocs_done=allocs,
            share_reached=bool(share),
            time_to_100pct_s=(round(done_all["t"] - t0, 4)
                              if done_all else None),
            jobs_complete=len(done_at), jobs=len(count),
            evals_seen=len(evals), evals_failed=evals.count("failed"),
            stream_resyncs=report["resyncs"],
            stream_errors=report["stream_errors"][:3])
        return {"t0": t0, "t1": t_stop, "allocs": allocs, "specs": specs,
                "share_reached": bool(share), "attempted": len(count),
                "failed_ids": sorted(report["ended_bad"]),
                "complete": set(done_at), "evals": evals}

    def run(self, seconds: float, on_open, on_clock_stop,
            on_round_end) -> dict:
        """-> the rounds' windows and what the client saw in them.
        `on_open(k)` just before round k's clock starts,
        `on_clock_stop(k, t0, t1)` just after it stops,
        `on_round_end(k, round)` once the round's client has reported
        (the harness reads the round's end state there: the next round
        purges it)."""
        done: list = []
        rearm_s = 0.0
        for k in range(self.rounds):
            left = seconds - sum(r["t1"] - r["t0"] for r in done)
            if done and (left <= 0 or not done[-1]["share_reached"]):
                break
            if done:
                t = time.perf_counter()
                try:
                    self._rearm(done[-1]["specs"], self.specs_of(k))
                except TimeoutError as e:
                    # a straggler on a follow-up timer outlived its job:
                    # what was measured stands, no further round is made
                    self.ctx.note("rounds", stopped_before=k, why=str(e))
                    break
                rearm_s += time.perf_counter() - t
            done.append(self._round(k, self.specs if k == 0
                                    else self.specs_of(k), left,
                                    on_open, on_clock_stop))
            on_round_end(k, done[-1])
        spent = sum(r["t1"] - r["t0"] for r in done)
        allocs = sum(r["allocs"] for r in done)
        first = done[0]
        self.ctx.note("rounds", asked=self.rounds, made=len(done),
                      seconds=round(spent, 4), allocs_done=allocs,
                      rearm_s=round(rearm_s, 3),
                      allocs_per_s=[round(r["allocs"] / (r["t1"] - r["t0"]), 1)
                                    for r in done])
        return {
            # per-layer observations are the first window's
            "t0": first["t0"], "t1": first["t1"],
            "windows": [(r["t0"], r["t1"]) for r in done],
            "rearm_s": rearm_s,
            "end_to_end": {"allocs_per_s": allocs / spent},
            "attempted": sum(r["attempted"] for r in done),
            "failed_ids": sorted(j for r in done for j in r["failed_ids"]),
            "client": {
                "out_of_attempts_pct": (
                    100.0 * first["evals"].count("failed")
                    / len(first["evals"]) if first["evals"] else None)},
        }

    def close(self) -> None:
        if self.child is not None:
            self.child.close()
