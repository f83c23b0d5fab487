"""Generator kind `system_rollout`: a system job rolled out onto a full
cluster, once a round.

The cluster is full before the window opens (the deploy kind loads the
standing population in set-up). A round is one system job, built here
from the traffic file's one class (upstream's mock.SystemJob, sized and
placed as the class says), registered over HTTP into a paused broker;
opening the window resumes the broker, and the clock stops when the
client sees the job's evaluation chain complete (`stop_share` 0.9 of
one job is the whole job). Its spec's `count` is the number of eligible
nodes, so `correct` holds the job to one live allocation a node.

`allocs_per_s` = placements of all rounds / all rounds' seconds, as
`backlog`'s. Between two rounds (set-up, `rearm_s`): the system job is
purged, the batch jobs it preempted place again in the room it leaves
(their blocked evaluations unblock), the cluster is drained, the
evicted allocations are reported complete as their clients would
report them, the store is collected (`nomad system gc`), and the
deploy kind checks that it is as
full as round 0 found it. Before each round's broker is resumed the
deploy kind reads the cluster as the round opens: its check at the
round's end compares every node's victims with the plain reference's
choice from that reading."""

from __future__ import annotations

import time

from benchmark.generators import backlog
from benchmark.generators.child import Child


def build_system_job(spec: dict):
    """Upstream's mock.SystemJob as the spec sizes it: priority, task
    ask, dynamic port labels, datacenters, constraints."""
    from nomad_tpu import mock
    from nomad_tpu.structs.constraint import Constraint
    from nomad_tpu.structs.resources import NetworkResource

    job = mock.system_job()
    job.id = job.name = spec["id"]
    job.priority = int(spec["priority"])
    job.datacenters = list(spec["datacenters"])
    job.constraints = [Constraint(**c) for c in spec["constraints"]]
    res = job.task_groups[0].tasks[0].resources
    res.cpu, res.memory_mb = int(spec["cpu"]), int(spec["mem"])
    res.networks = ([NetworkResource(dynamic_ports=list(spec["port_labels"]))]
                    if spec["port_labels"] else [])
    return job


class Generator(backlog.Generator):
    def specs_of(self, k: int) -> list:
        """Round k's one system job, its count the eligible nodes."""
        ctx = self.ctx
        cls = ctx.traffic["classes"][0]
        prefix = f"{ctx.cell['name']}-{ctx.seed}" + (f"-r{k}" if k else "")
        return [{"id": f"{prefix}-system", "type": "system",
                 "count": int(ctx.deployment.nodes),
                 "cpu": int(cls["cpu"][0]), "mem": int(cls["mem"][0]),
                 "ports": len(cls.get("port_labels", ())),
                 "priority": int(cls.get("priority", 100)),
                 "port_labels": list(cls.get("port_labels", ())),
                 "datacenters": list(cls["datacenters"]),
                 "constraints": list(cls.get("constraints", ())),
                 "spread": None}]

    def _load(self, specs: list) -> None:
        dep = self.ctx.deployment
        jobs = [build_system_job(s) for s in specs]
        t0 = time.perf_counter()
        dep.pause_broker(True)
        dep.submit(jobs)
        dep.open_round(jobs)
        self.ctx.note("backlog", jobs=len(jobs), sheds=0,
                      allocs=sum(s["count"] for s in specs),
                      submit_s=round(time.perf_counter() - t0, 3))
        self.child = Child(
            {"address": dep.address, "stop_share": self.share,
             "jobs": [{"id": s["id"], "count": s["count"]}
                      for s in specs]}, self.ctx.workdir)
        if self.child.wait_for("ready", 60.0) is None:
            raise RuntimeError("load generator not ready")

    def _rearm(self, done: list, specs: list) -> None:
        """The system job leaves, the preempted work comes back, the
        store is collected and the cluster checked full; then the next
        round's job is loaded."""
        from benchmark.deploy.single_agent_system import NotHeld

        dep = self.ctx.deployment
        ids = [s["id"] for s in done]
        dep.pause_broker(False)
        for job_id in ids:
            dep.api.deregister_job(job_id, purge=True)
        try:
            dep.drain(ids + dep.population_ids, timeout=60.0)
        except TimeoutError as e:
            # the preempted work did not come back: the cluster is not
            # as full as round 0 found it (not a short run, a failed one)
            dep.broken = True
            raise NotHeld(f"re-arm: {e} (the configuration's population)")
        settled = dep.settle_evicted()
        self.ctx.note("gc", evicted_settled=settled,
                      **{k: v for k, v in dep.api.system_gc().items()
                         if isinstance(v, (int, float))})
        dep.hold_full()
        self._load(specs)
