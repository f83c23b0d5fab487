"""Generator kind `backlog`: the whole load is registered over HTTP into
a paused broker during set-up; opening the window resumes the broker.

End-to-end metric: `allocs_per_s` = allocations of jobs whose evaluation
chain the client saw complete / seconds since the broker was resumed,
the clock stopping when jobs holding `stop_share` of the backlog's
allocations are complete, or at --seconds. A straggler on a follow-up
timer then costs its own allocations, not a tripled time; the time to
100% is printed beside it."""

from __future__ import annotations

import time

from benchmark import traffic as traffic_mod
from benchmark.generators.child import Child
from benchmark.jobs import build_job


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.specs = traffic_mod.job_specs(
            t, ctx.seed, int(t["jobs"]), f"{ctx.cell['name']}-{ctx.seed}")
        self.share = float(t.get("stop_share", 0.9))
        self.child = None

    def prepare(self) -> None:
        dep, t = self.ctx.deployment, self.ctx.traffic
        jobs = [build_job(s) for s in self.specs]
        t0 = time.perf_counter()
        dep.pause_broker(True)
        sheds = dep.submit(jobs, threads=int(t.get("submit_threads", 8)))
        self.ctx.note("backlog", jobs=len(jobs), sheds=sheds,
                      allocs=sum(s["count"] for s in self.specs),
                      submit_s=round(time.perf_counter() - t0, 3))
        self.child = Child(
            {"address": dep.address, "stop_share": self.share,
             "jobs": [{"id": s["id"], "count": s["count"]}
                      for s in self.specs]}, self.ctx.workdir)
        if self.child.wait_for("ready", 60.0) is None:
            raise RuntimeError("load generator not ready")

    def run(self, seconds: float, on_open, on_clock_stop) -> dict:
        dep = self.ctx.deployment
        on_open()
        t0 = time.time()
        dep.pause_broker(False)
        share = self.child.wait_for("share", seconds - (time.time() - t0))
        t_stop = share["t"] if share else t0 + seconds
        on_clock_stop(t0, t_stop)
        # the time to 100% is printed, not judged: wait for it a little
        # past the clock's stop, never past the window
        tail = float(self.ctx.traffic.get("tail_wait_seconds", 8))
        done_all = self.child.wait_for("all", max(0.0, min(
            seconds - (time.time() - t0), t_stop + tail - time.time())))
        report = self.child.finish()
        count = {s["id"]: s["count"] for s in self.specs}
        done_at = report["done_at"]
        allocs = sum(count[j] for j, t in done_at.items() if t <= t_stop)
        evals = [s for rows in report["evals"].values() for s in rows]
        window_s = t_stop - t0
        # [seconds, allocations] when each share of the backlog was complete:
        # printed, not judged (what a lower or higher stop share reads)
        total, acc, reached = sum(count.values()), 0, {}
        shares = (0.1, 0.25, 0.5, 0.75, 0.9, 1.0)
        for j, t in sorted(done_at.items(), key=lambda kv: kv[1]):
            acc += count[j]
            for q in shares:
                if q not in reached and acc >= q * total:
                    reached[q] = [round(t - t0, 3), acc]
        self.ctx.note("timeline", **{f"at_{int(100 * q)}pct":
                                     reached.get(q) for q in shares})
        self.ctx.note(
            "window", seconds=round(window_s, 4), allocs_done=allocs,
            share_reached=bool(share),
            time_to_100pct_s=(round(done_all["t"] - t0, 4)
                              if done_all else None),
            jobs_complete=len(done_at), jobs=len(count),
            evals_seen=len(evals), evals_failed=evals.count("failed"),
            stream_resyncs=report["resyncs"],
            stream_errors=report["stream_errors"][:3])
        return {
            "t0": t0, "t1": t_stop,
            "end_to_end": {"allocs_per_s": allocs / window_s},
            "attempted": len(count),
            "failed_ids": sorted(report["ended_bad"]),
            "complete": set(done_at),
            "client": {
                "out_of_attempts_pct": (100.0 * evals.count("failed")
                                        / len(evals) if evals else None)},
        }

    def close(self) -> None:
        if self.child is not None:
            self.child.close()
