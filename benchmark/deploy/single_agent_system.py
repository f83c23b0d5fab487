"""Deploy kind `single_agent_system`: `single_agent_live` on a cluster
that is full before the window opens, held to the guarantees of a
system job that preempts (configs/grid-10k-system.json).

Set-up (`warm`): the system path's own warm-up on the empty fleet (a
system job of 1 MHz / 1 MB with the cell's port on every node, purged
and collected again), then the standing population over HTTP, tier by
tier into a paused broker (the service tier, then the batch tier in
what the service tier leaves), and the check that every eligible node
needs a preemption (`hold_full`: no node keeps the system job's cpu
free, and every population job is whole). `hold_full` runs again at
every re-arm: the cluster is as full as round 0 found it. Before the
re-arm's collection `settle_evicted` reports the evicted allocations
complete, as the nodes' clients would.

At every `quiesce()` (a round's end, outside the window), on the
quiesced store and on ALL nodes, against the reading of the cluster
the generator took as the round opened (`open_round`):

(a) the round's system job holds one live allocation on every eligible
    node the plain reference can free, and none where it cannot;
(b) each node's victims (allocations evicted for the round's system
    job), as the multiset of (job type, priority, cpu, mem), equal what
    benchmark/reference/preemption.py takes from the node's allocations
    at the round's opening: equal fillers are interchangeable;
(c) no node over its capacity in any dimension, recounted;
(d) every job that lost an allocation has an evaluation triggered by
    `preemption`, created in the round, and no victim is desired `run`;
(e) the port line over every allocation that holds a port, by
    benchmark/reference/ports.py.

A violation raises `NotHeld`: the harness prints the traceback and
exits with code 1 and no result line, and `stop()` ends the process at
once, as `single_agent_ports` does. One `[system]` line a round says
what was read, so a pass over nothing shows. `single_agent_live`'s own
liveness rule is not applied: the preempted batch jobs' blocked
evaluations wait, by design, for the room the system job holds, and
their return is what `hold_full` checks at the re-arm."""

from __future__ import annotations

import os
import sys
import time
from collections import Counter

import numpy as np

from benchmark.deploy import single_agent_live, single_agent_ports
from benchmark.reference import ports as port_reference
from benchmark.reference import preemption as reference


TRAFFIC = "system.preempt"


class NotHeld(RuntimeError):
    """A guarantee of the configuration does not hold in this run."""


def _sized(config: dict, toy: bool) -> dict:
    return config["toy"] if toy and "population" in config["toy"] \
        else config


class Deployment(single_agent_live.Deployment):
    broken = False      # a guarantee did not hold: the run has failed

    def __init__(self, config: dict, seed: int, toy: bool):
        super().__init__(config, seed, toy)
        self.tiers = _sized(config, toy)["population"]["tiers"]
        self.population_ids: list = []
        self.population: dict = {}
        self.opened = None
        self.rounds_checked = 0

    # -- set-up ----------------------------------------------------------

    def warm(self, warm_specs: dict, scatter_buckets: bool) -> dict:
        from benchmark.generators.system_rollout import build_system_job

        t0 = time.perf_counter()
        cls = self.traffic_class
        job = build_system_job({
            "id": f"warm-{self.seed}-system", "priority": cls["priority"],
            "datacenters": cls["datacenters"],
            "constraints": cls.get("constraints", ()), "cpu": 1, "mem": 1,
            "port_labels": cls.get("port_labels", ())})
        self.warm_ids = {job.id}
        self.submit([job])
        self.drain([job.id])
        self.api.deregister_job(job.id, purge=True)
        self.drain([job.id])
        self.api.system_gc()
        self.timings["warm_system_s"] = time.perf_counter() - t0
        if scatter_buckets:
            self._warm_scatter()
        t0 = time.perf_counter()
        k = self.warm_refill()
        self.timings["warm_refill_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        n = self.load_population()
        self.timings["population_s"] = time.perf_counter() - t0
        self.hold_full()
        self.timings["warm_s"] = (self.timings["warm_system_s"]
                                  + self.timings["warm_refill_s"]
                                  + self.timings["population_s"])
        return {"jobs": 1 + k + n}

    def warm_refill(self) -> int:
        """The shapes of the preempted jobs' evaluations, on the empty
        fleet: a batch filler's job asks again for what the system job
        took from it, about one filler in four of its count (a node
        holds four), so every padded length from an eighth of the
        tier's count up to its count is placed once here and purged
        again (the population's own load places the full count; the
        service tier loses nothing). -> jobs placed."""
        from benchmark.jobs import build_job
        from nomad_tpu.tensor.cluster import _pad_pow2

        dcs = list(self.config["node_mix"]["datacenters"])
        specs = []
        for tier in self.tiers:
            if tier["type"] != "batch":
                continue
            count = int(tier["count"])
            k = _pad_pow2(max(1, count // 8))
            while k < count:
                specs.append({"id": f"warm-{self.seed}-{tier['name']}-{k}",
                              "type": tier["type"], "count": k,
                              "cpu": int(tier["cpu"]), "mem": int(tier["mem"]),
                              "ports": 0, "spread": None, "datacenters": dcs,
                              "constraints": []})
                k *= 2
        jobs = [build_job(s) for s in specs]
        ids = [j.id for j in jobs]
        self.warm_ids.update(ids)
        self.submit(jobs, threads=4)
        self.drain(ids)
        for job_id in ids:
            self.api.deregister_job(job_id, purge=True)
        self.drain(ids)
        self.api.system_gc()
        return len(jobs)

    @property
    def traffic_class(self) -> dict:
        """The system job's class of the one traffic mix this kind
        serves (its warm-up job and its ask come from it)."""
        import json
        from pathlib import Path

        if not hasattr(self, "_cls"):
            path = (Path(__file__).resolve().parents[1] / "traffic"
                    / f"{TRAFFIC}.json")
            with open(path) as f:
                traffic = json.load(f)
            if self.toy:
                traffic.update(traffic.get("toy", {}))
            self._cls = traffic["classes"][0]
        return self._cls

    def load_population(self) -> int:
        """The standing population, tier by tier: registered into a
        paused broker, resumed, drained."""
        from benchmark.jobs import build_job

        dcs = list(self.config["node_mix"]["datacenters"])
        for tier in self.tiers:
            specs = [{"id": f"pop-{self.seed}-{tier['name']}-{j:03d}",
                      "type": tier["type"], "count": int(tier["count"]),
                      "cpu": int(tier["cpu"]), "mem": int(tier["mem"]),
                      "ports": 0, "spread": None, "datacenters": dcs,
                      "constraints": []}
                     for j in range(int(tier["jobs"]))]
            jobs = [build_job(s) for s in specs]
            self.pause_broker(True)
            try:
                self.submit(jobs, threads=8)
            finally:
                self.pause_broker(False)
            self.drain([j.id for j in jobs])
            for s in specs:
                self.population[s["id"]] = s["count"]
                self.population_ids.append(s["id"])
        return len(self.population_ids)

    # -- reading the cluster (the store's index entries, as plain data) --

    def _read(self, snap) -> dict:
        """{node id: [{"id", "type", "priority", "vec", "max_parallel",
        "group", "job", "ports", "desired", "by"}]} of every live
        allocation: blocks as columns, a promoted position as its row."""
        from nomad_tpu.structs.alloc import BLOCK_SEP

        store, index = snap._store, snap.index
        out: dict = {n.id: [] for n in snap.nodes()}
        promoted: dict = {}
        rows = []
        info: dict = {}

        def about(job, tg_name):
            key = (id(job), tg_name)
            if key not in info:
                tg = job.lookup_task_group(tg_name) if job else None
                mp = (tg.migrate.max_parallel
                      if tg is not None and tg.migrate is not None else 0)
                info[key] = (job.type if job else "", job.priority
                             if job else 0, max(0, int(mp)))
            return info[key]

        for aid, a in store._allocs.iterate(index):
            sep = aid.rfind(BLOCK_SEP)
            if sep >= 0:
                promoted.setdefault(aid[:sep], set()).add(int(aid[sep + 1:]))
            rows.append(a)
        for block in snap.alloc_blocks():
            jtype, prio, mp = about(block.job, block.task_group)
            group = (block.namespace, block.job_id, block.task_group)
            off = block.offsets()
            gone = promoted.get(block.id, set()) | set(block.dropped)
            vec = np.asarray(block.allocated_vec, np.float64)
            for m in block.live_rows():
                node = out.setdefault(block.node_ids[m], [])
                for p in range(int(off[m]), int(off[m + 1])):
                    if p in gone:
                        continue
                    node.append({
                        "id": f"{block.id}{BLOCK_SEP}{p}", "type": jtype,
                        "priority": prio, "vec": vec, "max_parallel": mp,
                        "group": group, "job": block.job_id,
                        "ports": ([] if block.ports is None else
                                  [[label, v] for label, v in zip(
                                      block.ports.labels,
                                      block.ports.values[p].tolist())]),
                        "desired": "run", "by": ""})
        evicted = []
        for a in rows:
            jtype, prio, mp = about(a.job, a.task_group)
            plain = {"id": a.id, "type": jtype, "priority": prio,
                     "vec": np.asarray(a.allocated_vec, np.float64),
                     "max_parallel": mp,
                     "group": (a.namespace, a.job_id, a.task_group),
                     "job": a.job_id,
                     "ports": [[p.label, p.value]
                               for p in a.allocated_ports or ()],
                     "desired": a.desired_status,
                     "by": a.preempted_by_allocation or "",
                     "node": a.node_id}
            if a.terminal_status():
                if plain["by"]:
                    evicted.append(plain)
                continue
            out.setdefault(a.node_id, []).append(plain)
        return {"live": out, "evicted": evicted}

    def _eligible(self, snap, job) -> dict:
        """{node id: capacity open to a placement} of the ready nodes
        in the job's datacenters that meet its constraints (equality
        on an attribute, the only operand the cell's job names)."""
        out = {}
        for node in snap.nodes():
            if node.status != "ready" or node.drain \
                    or node.datacenter not in job.datacenters:
                continue
            ok = True
            for c in job.constraints:
                key = c.ltarget.strip("${}").partition(".")[2]
                ok &= node.attributes.get(key) == c.rtarget
            if ok:
                out[node.id] = np.asarray(node.available_vec(), np.float64)
        return out

    def settle_evicted(self) -> int:
        """What a node's client does with an evicted allocation: it stops
        the allocation and reports it complete, after which `nomad
        system gc` collects it (the cell's fleet runs no client). Part
        of the re-arm, so that every round opens on a store without the
        rounds before it. -> allocations reported."""
        from nomad_tpu.structs import enums
        from nomad_tpu.structs.alloc import Allocation

        snap = self.server.store.snapshot()
        try:
            ids = [a.id for _, a in snap._store._allocs.iterate(snap.index)
                   if a.desired_status == enums.ALLOC_DESIRED_EVICT
                   and not a.client_terminal()]
        finally:
            snap.close()
        self.server.update_allocs_from_client([
            Allocation(id=aid, client_status=enums.ALLOC_CLIENT_COMPLETE)
            for aid in ids])
        return len(ids)

    def hold_full(self) -> None:
        """Every eligible node needs a preemption to take the system
        job (less than its cpu free), and every job of the population
        holds its count of live allocations (a block position evicted
        and promoted to its row is not live)."""
        snap = self.server.store.snapshot()
        try:
            ask = float(self.traffic_class["cpu"][0])
            read = self._read(snap)["live"]
            caps = {n.id: np.asarray(n.available_vec(), np.float64)
                    for n in snap.nodes()}
        finally:
            snap.close()
        roomy = [nid for nid, cap in caps.items()
                 if cap[0] - sum(a["vec"][0] for a in read.get(nid, ()))
                 >= ask]
        held = Counter(a["job"] for allocs in read.values() for a in allocs)
        short = {j: c - held[j] for j, c in self.population.items()}
        short = {j: n for j, n in short.items() if n}
        print(f"[system] full nodes={len(caps)} roomy={len(roomy)} "
              f"population_jobs={len(self.population)} "
              f"population_allocs={sum(self.population.values())} "
              f"short={sum(short.values())}", flush=True)
        if roomy or short:
            self.broken = True
            raise NotHeld(
                f"set-up: {len(roomy)} node(s) keep {ask} MHz free (e.g. "
                f"{roomy[:3]}) and {len(short)} population job(s) are "
                f"short (e.g. {sorted(short.items())[:3]}): the cluster is "
                "full before the window opens, every eligible node needs a "
                "preemption (the configuration's population)")

    def open_round(self, jobs) -> None:
        """The cluster as the round opens (the broker is paused, the
        round's system job registered): what (a)-(e) compare with."""
        snap = self.server.store.snapshot()
        try:
            job = jobs[0]
            self.opened = {
                "index": snap.index, "job": job,
                "ask": np.asarray(
                    job.task_groups[0].combined_resources().vec(),
                    np.float64),
                "eligible": self._eligible(snap, job),
                "live": self._read(snap)["live"]}
        finally:
            snap.close()

    # -- the round's end -------------------------------------------------

    def hold_liveness(self) -> None:
        """Not applied here (module doc)."""

    def quiesce(self, timeout: float = 10.0) -> bool:
        try:
            quiet = super().quiesce(timeout)
            self.hold_guarantees()
        except NotHeld:
            self.broken = True
            raise
        return quiet

    def stop(self) -> None:
        """A run whose guarantee broke has printed its traceback (the
        harness does, before it stops the deployment) and exits here,
        with code 1 and no result line."""
        if self.broken:
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)
        super().stop()

    def hold_guarantees(self) -> None:
        opened = self.opened
        job = opened["job"]
        snap = self.server.store.snapshot()
        try:
            now = self._read(snap)
            preemption_evals = {
                ev.job_id for ev in snap.evals()
                if ev.triggered_by == "preemption"
                and ev.create_index > opened["index"]}
            nodes = single_agent_ports.plain_nodes(snap)
        finally:
            snap.close()
        live, evicted = now["live"], now["evicted"]
        eligible, ask = opened["eligible"], opened["ask"]
        bad: list = []

        # the system job's allocations, and what they preempted
        mine = {nid: [a for a in allocs if a["job"] == job.id]
                for nid, allocs in live.items()}
        ids = {a["id"] for allocs in mine.values() for a in allocs}
        victims: dict = {}
        for a in evicted:
            if a["by"] in ids:
                victims.setdefault(a["node"], []).append(a)

        # the reference, node by node in id order (the order carries
        # max_parallel counts from node to node; no allocation of this
        # cell has a max_parallel)
        counts: dict = {}
        memo: dict = {}
        want_placed = 0
        want_victims = 0
        for nid in sorted(eligible):
            allocs = opened["live"].get(nid, [])
            coupled = any(a["max_parallel"] for a in allocs)
            key = None if coupled else (
                tuple(eligible[nid].tolist()),
                tuple(sorted((a["type"], a["priority"],
                              tuple(a["vec"].tolist())) for a in allocs)))
            if key is not None and key in memo:
                got = memo[key]
            else:
                chosen = reference.choose(eligible[nid], allocs, ask,
                                          job.priority, counts)
                by_id = {a["id"]: a for a in allocs}
                got = (None if chosen is None else Counter(
                    (by_id[v]["type"], by_id[v]["priority"],
                     by_id[v]["vec"][0], by_id[v]["vec"][1])
                    for v in chosen))
                if key is not None:
                    memo[key] = got
            held = len(mine.get(nid, ()))
            if got is None:
                if held:
                    bad.append(f"{nid}: {held} allocation(s) of the system "
                               "job where no victims make room")
                continue
            want_placed += 1
            want_victims += sum(got.values())
            if held != 1:
                bad.append(f"{nid}: {held} live allocation(s) of the system "
                           "job, not one")
            have = Counter((a["type"], a["priority"], a["vec"][0],
                            a["vec"][1]) for a in victims.get(nid, ()))
            if have != got:
                bad.append(f"{nid}: victims {dict(have)}, the reference's "
                           f"{dict(got)}")
        for nid in set(victims) - set(eligible):
            bad.append(f"{nid}: victims on a node that is not eligible")

        # (c) capacity, recounted
        over = 0
        for nid, allocs in live.items():
            cap = eligible.get(nid)
            if cap is None:
                continue
            used = sum((a["vec"] for a in allocs), np.zeros_like(cap))
            if (used > cap + 1e-6).any():
                over += 1
                bad.append(f"{nid}: over capacity, {used.tolist()} of "
                           f"{cap.tolist()}")

        # (d) the preempted jobs' evaluations; no victim still run
        lost = {a["job"] for allocs in victims.values() for a in allocs}
        for j in sorted(lost - preemption_evals):
            bad.append(f"{j}: lost allocation(s) to preemption and has no "
                       "evaluation triggered by preemption")
        running = [a["id"] for allocs in victims.values() for a in allocs
                   if a["desired"] == "run"]
        if running:
            bad.append(f"victims still desired run: {running[:3]}")

        # (e) ports
        labels = [label for net in job.task_groups[0].combined_resources()
                  .networks for label in net.dynamic_ports]
        held_ports = [{"id": a["id"], "node": nid, "ports": a["ports"],
                       "dynamic": labels if a["job"] == job.id else
                       [p[0] for p in a["ports"]], "static": []}
                      for nid, allocs in live.items() for a in allocs
                      if a["ports"] or a["job"] == job.id]
        census = port_reference.census(nodes, held_ports)
        port_bad = port_reference.violations(nodes, held_ports)
        bad.extend(port_bad)

        n_victims = sum(map(len, victims.values()))
        types = ",".join(f"{t}:{n}" for t, n in sorted(Counter(
            a["type"] for v in victims.values() for a in v).items())) \
            or "none"
        print(f"[system] round={self.rounds_checked} "
              f"eligible={len(eligible)} placed={len(ids)} "
              f"reference_placed={want_placed} victims={n_victims} "
              f"reference_victims={want_victims} "
              f"victim_types={types} "
              f"jobs_preempted={len(lost)} preemption_evals="
              f"{len(lost & preemption_evals)} over_capacity={over} "
              + " ".join(f"{k}={v}" for k, v in census.items())
              + f" violations={len(bad)}", flush=True)
        self.rounds_checked += 1
        if bad:
            raise NotHeld(
                f"{len(bad)} violation(s) of the system job's guarantees, "
                f"e.g. {bad[:4]} (the configuration's guarantees)")


def deploy(config: dict, seed: int, toy: bool) -> Deployment:
    return Deployment(config, seed, toy)
