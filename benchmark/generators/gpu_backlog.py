"""Generator kind `gpu_backlog`: `backlog` whose jobs carry a `device`
block.

The traffic file's class names its task's device asks under `devices`,
as Nomad's job specification writes them (`device "nvidia/gpu" {
count = 1; constraint {...}; affinity {...} }`):

    {"name": "nvidia/gpu", "count": 1,
     "constraints": [[ltarget, operand, rtarget], ...],
     "affinities": [[ltarget, operand, rtarget, weight], ...]}

Everything else is `backlog`'s: the specs, the rounds, the clock.
`benchmark/jobs.py` builds a spec's job without a device; this kind
adds the block to what it builds (`with_devices`), and the deploy kind
`single_agent_gpu` does the same for its warm-up's jobs."""

from __future__ import annotations

import time

from benchmark.generators import backlog
from benchmark.generators.child import Child
from benchmark.jobs import build_job


def requested_devices(asks: list) -> list:
    """A class's (or a task ask's) `devices` as the program's asks."""
    from nomad_tpu.structs import Affinity, Constraint
    from nomad_tpu.structs.resources import RequestedDevice

    return [RequestedDevice(
        name=ask["name"], count=int(ask["count"]),
        constraints=[Constraint(ltarget=l, operand=op, rtarget=r)
                     for l, op, r in ask.get("constraints", ())],
        affinities=[Affinity(ltarget=l, operand=op, rtarget=r,
                             weight=int(w))
                    for l, op, r, w in ask.get("affinities", ())])
            for ask in asks]


def with_devices(job, asks: list):
    """`job` (benchmark/jobs.py's) with its one task asking `asks`."""
    job.task_groups[0].tasks[0].resources.devices = requested_devices(asks)
    return job


class Generator(backlog.Generator):
    def _load(self, specs: list) -> None:
        dep, t = self.ctx.deployment, self.ctx.traffic
        asks = t["classes"][0]["devices"]
        jobs = [with_devices(build_job(s), asks) for s in specs]
        t0 = time.perf_counter()
        dep.pause_broker(True)
        sheds = dep.submit(jobs, threads=int(t.get("submit_threads", 8)))
        self.ctx.note("backlog", jobs=len(jobs), sheds=sheds,
                      allocs=sum(s["count"] for s in specs),
                      devices=sum(a["count"] for a in asks)
                      * sum(s["count"] for s in specs),
                      submit_s=round(time.perf_counter() - t0, 3))
        self.child = Child(
            {"address": dep.address, "stop_share": self.share,
             "jobs": [{"id": s["id"], "count": s["count"]}
                      for s in specs]}, self.ctx.workdir)
        if self.child.wait_for("ready", 60.0) is None:
            raise RuntimeError("load generator not ready")
