"""Deploy kind `single_agent_gpu`: `single_agent_ports` on a fleet of
which some nodes carry GPUs, held to the guarantees of a configuration
whose jobs ask a device (configs/grid-10k-gpu.json).

Set-up: the fleet as `single_agent` builds it, with upstream's
mock.NvidiaNode() device group (two NVIDIA 1080 Ti, attributes with
units; `nomad_tpu.mock.add_nvidia_gpus`, instance ids drawn from the
seed) added to every node the configuration's `gpu_nodes` rule picks,
before registration; then the warm-up of the cell's shapes with the
device ask on every warm job (`task_ask.devices`): a job without it
compiles another program.

At every `quiesce()` (a round's end, outside the window), after
`single_agent_ports`' checks (every live job whole where there is room,
no evaluation `failed`, the port line) and on the same quiesced store,
over ALL nodes and every live allocation, by the plain reference
`benchmark/reference/devices.py`:

(d) every allocation of a group that asks a device holds exactly the
    instances it asked, of a group on its node that matches the ask's
    name and meets its constraints; no instance id twice on a node,
    none that is not its node's; no node over capacity;
(e) every live spread job even over the racks: max - min within the
    configuration's `spread_slack`.

One `[gpu]` line a round says what was read. A violation raises
`NotHeld`, and the run ends with exit code 1 and no result line, as
`single_agent_ports` ends one.
"""

from __future__ import annotations

import random
import time
import uuid

from benchmark.deploy import single_agent, single_agent_ports
from benchmark.deploy.single_agent_ports import NotHeld
from benchmark.generators.gpu_backlog import with_devices
from benchmark.reference import devices as reference


def carries_gpus(i: int, rule: dict) -> bool:
    """The configuration's `gpu_nodes` rule for node number `i`:
    i % every < first."""
    return i % int(rule["every"]) < int(rule["first"])


def plain_nodes(snap) -> dict:
    return {n.id: {"cpu": float(n.resources.cpu),
                   "mem": float(n.resources.memory_mb),
                   "rack": n.meta.get("rack", ""),
                   "groups": [{"id": g.id, "vendor": g.vendor,
                               "type": g.type, "name": g.name,
                               "instances": list(g.instance_ids),
                               "attributes": dict(g.attributes)}
                              for g in n.resources.devices]}
            for n in snap.nodes()}


def plain_allocs(snap) -> list:
    """Every live allocation as the reference takes it, beside its
    group's device asks and cpu / memory ask."""
    asks: dict = {}

    def ask_of(a):
        key = (a.namespace, a.job_id, a.task_group)
        if key not in asks:
            job = a.job or snap.job_by_id(a.job_id, a.namespace)
            tg = next(g for g in job.task_groups if g.name == a.task_group)
            res = tg.combined_resources()
            asks[key] = (float(res.cpu), float(res.memory_mb), [
                {"name": d.name, "count": int(d.count),
                 "constraints": [[c.ltarget, c.operand, c.rtarget]
                                 for c in d.constraints]}
                for d in res.devices])
        return asks[key]

    out = []
    for a in snap.allocs():
        if a.terminal_status():
            continue
        cpu, mem, devices = ask_of(a)
        out.append({"id": a.id, "node": a.node_id, "job": a.job_id,
                    "cpu": cpu, "mem": mem, "asks": devices,
                    "devices": {gid: list(ids) for gid, ids in
                                (a.allocated_devices or {}).items()}})
    return out


class Deployment(single_agent_ports.Deployment):
    def start(self) -> None:
        """single_agent's start, with the GPU groups added to the nodes
        the rule picks before the fleet registers."""
        from nomad_tpu import cli
        from nomad_tpu.api.client import ApiClient
        from nomad_tpu.chaos.swarm import Swarm
        from nomad_tpu.mock import add_nvidia_gpus

        flags = dict(self.config["agent"])
        if self.toy:
            flags.update(self.config["toy"].get("agent", {}))
        argv = ["agent", "--port", "0"]
        for k, v in flags.items():
            argv += [f"--{k}", str(v)]
        t0 = time.perf_counter()
        self.agent = cli.Agent(cli.build_parser().parse_args(argv))
        print(self.agent.start_line, flush=True)
        self.device = self.agent.device
        self.server = self.agent.server
        self.address = self.agent.http.address
        self.api = ApiClient(address=self.address)
        self.timings["agent_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        rng = random.Random(self.seed)
        ids = random.Random(f"gpus-{self.seed}")
        mix, rule = self.config["node_mix"], self.config["gpu_nodes"]
        swarm = Swarm(lambda: self.server, self.nodes,
                      ttl=self.server.config.heartbeat_ttl,
                      prefix=f"n{self.seed}")
        for i, sn in enumerate(swarm.nodes):
            single_agent.shape_node(sn.node, i, rng, mix)
            if carries_gpus(i, rule):
                add_nvidia_gpus(sn.node, [
                    str(uuid.UUID(int=ids.getrandbits(128)))
                    for _ in range(int(rule["instances"]))])
        swarm.start()       # drivers first: TTL timers arm at registration
        self.swarm = swarm
        done = swarm.register_all()
        if done != self.nodes:
            raise RuntimeError(f"registered {done} of {self.nodes} nodes")
        self.timings["fleet_s"] = time.perf_counter() - t0

    def warm(self, warm_specs: dict, scatter_buckets: bool) -> dict:
        """The scan's shapes, each by a warm job that asks the cell's
        device (another number of extra columns is another program),
        racing, then purged; then the scatter buckets. The traffic's
        class spreads, so no warm job takes the count solve. A warm job
        whose evaluation blocks, or the warm-up past its minute, ends
        the run: a tree that cannot place the device ask ends here, not
        at the window's time limit."""
        from benchmark.jobs import build_job

        self.warm_ids = {s["id"] for group in warm_specs.values()
                         for s in group}
        if warm_specs["burst"] or any(s["id"].endswith("-bulk")
                                      for s in warm_specs["singles"]):
            raise ValueError("single_agent_gpu warms the scan only")
        asks = self.config["task_ask"]["devices"]
        t0 = time.perf_counter()
        jobs = [with_devices(build_job(s), asks)
                for s in warm_specs["singles"]]
        ids = [j.id for j in jobs]
        if jobs:
            self.submit(jobs, threads=4)
            self._settle(ids)
            for job_id in ids:
                self.api.deregister_job(job_id, purge=True)
            self.drain(ids, timeout=60.0)
        self.timings["warm_scan_s"] = time.perf_counter() - t0
        if scatter_buckets:
            self._warm_scatter()
        self.timings["warm_s"] = time.perf_counter() - t0
        return {"jobs": len(jobs)}

    def _settle(self, job_ids: list, timeout: float = 60.0) -> None:
        deadline = time.time() + timeout
        while True:
            idle = self.server.wait_for_idle(timeout=1.0,
                                             include_delayed=False)
            snap = self.server.store.snapshot()
            try:
                still = [ev for j in job_ids for ev in snap.evals_by_job(j)
                         if not ev.terminal_status()]
            finally:
                snap.close()
            if idle and not still:
                return
            blocked = [ev for ev in still if ev.status == "blocked"]
            if (idle and blocked) or time.time() > deadline:
                self.broken = True
                raise NotHeld(
                    f"warm-up: {len(blocked)} blocked and "
                    f"{len(still) - len(blocked)} other open evaluation(s) "
                    f"of {len(job_ids)} warm job(s) asking "
                    f"{[a['name'] for a in self.config['task_ask']['devices']]}"
                    f", e.g. {[(ev.job_id, ev.status) for ev in still[:3]]}:"
                    " a job submitted to a cluster with room for all of it "
                    "is placed (the configuration's guarantees)")

    def hold_guarantees(self) -> None:
        super().hold_guarantees()
        snap = self.server.store.snapshot()
        try:
            spread_jobs = {j.id for j in snap.jobs()
                           if not j.stop and j.id not in self.warm_ids
                           and any(tg.spreads for tg in j.task_groups)}
            nodes = plain_nodes(snap)
            allocs = plain_allocs(snap)
        finally:
            snap.close()
        sized = self.config["toy"] if self.toy else self.config
        slack = int(sized["spread_slack"])
        read = reference.census(nodes, allocs)
        bad = (reference.violations(nodes, allocs)
               + reference.spread_violations(nodes, allocs, spread_jobs,
                                             slack))
        print(f"[gpu] round={self.rounds_checked - 1} "
              + " ".join(f"{k}={v}" for k, v in read.items())
              + f" spread_jobs={len(spread_jobs)} violations={len(bad)}",
              flush=True)
        if bad:
            self.broken = True
            raise NotHeld(
                f"devices: {len(bad)} violation(s) over "
                f"{read['allocations']} allocation(s) on {read['nodes']} "
                f"node(s), e.g. {bad[:3]}: every allocation of a group "
                "that asks a device holds exactly the instances it asked, "
                "of a group on its node that matches the ask and meets its "
                "constraints; no instance id twice on a node; no node over "
                "capacity; a spread job even over the racks (the "
                "configuration's guarantees)")


def deploy(config: dict, seed: int, toy: bool) -> Deployment:
    return Deployment(config, seed, toy)
