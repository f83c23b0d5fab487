"""Deploy kind `single_agent_ports`: `single_agent_live` held to the
guarantees a configuration whose jobs carry ports states.

At every `quiesce()` (a round's end, outside the window), after
`single_agent_live`'s own check and on the same quiesced store:

(a) every live job, the warm-up's aside, has `count` live allocations,
    unless the placed plus the missing exceed `allocations_that_fit`
    ("a job submitted to a cluster with room for all of it is placed");
(b) "... and none of its evaluations ends `failed`": an evaluation that
    ran out of plan attempts leaves its job to a follow-up on a 60 s
    timer, which the client that follows the job never sees complete;
(c) the port line, over ALL nodes and every live allocation, by the
    plain reference `benchmark/reference/ports.py`: exactly the ports
    asked, inside the node's dynamic range, outside its reserved ports,
    no value twice on a node.

A violation raises `NotHeld`: the harness prints the traceback and
exits with code 1 and no result line, as for `NotLive`. Such a run ends
at once (`stop()`): what is left of it is an agent whose 24 workers
still race for ports they cannot get, and stopping that one gracefully
waits for each of them (the tree before PR 38 took about 90 s a run
on the chip that way, 40 of them after its verdict was printed, where a
run is allowed 90; 63-67 s with this). Why here and
not in `correct`: a job the client never saw complete is not checked
there, so a program whose racing evaluations hand out one port twice
(the tree before PR 38: the applier throws their rows away until they
are out of attempts) "runs" the cell at some 30 allocations a second
with `correct: true`, and the gate would hold the change to a spread of
a fifth of that. One `[ports]` line a round says what was read, so a
pass over nothing shows.
"""

from __future__ import annotations

import os
import sys

from benchmark.deploy import single_agent_live
from benchmark.reference import ports as reference


class NotHeld(RuntimeError):
    """A guarantee of the configuration does not hold in this run."""


def plain_nodes(snap) -> dict:
    return {n.id: {"min": n.resources.min_dynamic_port,
                   "max": n.resources.max_dynamic_port,
                   "reserved": set(n.reserved.reserved_ports)}
            for n in snap.nodes()}


def plain_allocs(snap) -> list:
    """Every live allocation as the reference takes it: rows as they
    are, a block's live positions one by one (with no port: a block
    carries none), each beside what its group asked."""
    asks: dict = {}

    def ask_of(a):
        key = (a.namespace, a.job_id, a.task_group)
        if key not in asks:
            job = a.job or snap.job_by_id(a.job_id, a.namespace)
            tg = next(g for g in job.task_groups if g.name == a.task_group)
            res = tg.combined_resources()
            asks[key] = (
                [label for net in res.networks
                 for label in net.dynamic_ports],
                [[label, port] for label, port in res.reserved_port_asks()])
        return asks[key]

    out = []
    for a in snap.allocs():
        if a.terminal_status():
            continue
        dynamic, static = ask_of(a)
        out.append({"id": a.id, "node": a.node_id,
                    "ports": [[p.label, p.value]
                              for p in a.allocated_ports or ()],
                    "dynamic": dynamic, "static": static})
    return out


class Deployment(single_agent_live.Deployment):
    rounds_checked = 0
    broken = False      # a guarantee did not hold: the run has failed

    def quiesce(self, timeout: float = 10.0) -> bool:
        try:
            quiet = super().quiesce(timeout)
            self.hold_guarantees()
        except (NotHeld, single_agent_live.NotLive):
            self.broken = True
            raise
        return quiet

    def stop(self) -> None:
        """A run whose guarantee broke has printed its traceback (the
        harness does, before it stops the deployment) and exits here,
        with code 1 and no result line, without waiting for workers
        that are still out of ports to finish their evaluations."""
        if self.broken:
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)
        super().stop()

    def hold_guarantees(self) -> None:
        from benchmark.check import live_count

        snap = self.server.store.snapshot()
        try:
            jobs = {(j.namespace, j.id): j for j in snap.jobs()
                    if not j.stop and j.id not in self.warm_ids}
            live = {key: live_count(snap, key[1], key[0]) for key in jobs}
            failed = [ev for ev in snap.evals() if ev.status == "failed"
                      and (ev.namespace, ev.job_id) in jobs]
            nodes = plain_nodes(snap)
            allocs = plain_allocs(snap)
        finally:
            snap.close()
        read = reference.census(nodes, allocs)
        bad = reference.violations(nodes, allocs)
        print(f"[ports] round={self.rounds_checked} jobs={len(jobs)} "
              + " ".join(f"{k}={v}" for k, v in read.items())
              + f" violations={len(bad)}", flush=True)
        self.rounds_checked += 1
        short = {key: sum(tg.count for tg in job.task_groups) - live[key]
                 for key, job in jobs.items()}
        short = {key: n for key, n in short.items() if n}
        placed, missing = sum(live.values()), sum(short.values())
        if short and placed + missing <= self.fits:
            raise NotHeld(
                f"placement: {len(short)} of {len(jobs)} live job(s) are "
                f"not whole (e.g. {sorted(short.items())[:3]}): they miss "
                f"{missing} allocation(s) with {placed} placed and room "
                f"for {self.fits - placed} more of the {self.fits} that "
                "fit: a job submitted to a cluster with room for all of "
                "it is placed (the configuration's guarantees)")
        if failed:
            raise NotHeld(
                f"attempts: {len(failed)} evaluation(s) of "
                f"{len({(ev.namespace, ev.job_id) for ev in failed})} live "
                "job(s) ended `failed` (e.g. "
                f"{[(ev.job_id, ev.status_description) for ev in failed[:3]]}"
                "): none of a job's evaluations ends `failed` (the "
                "configuration's guarantees)")
        if bad:
            raise NotHeld(
                f"ports: {len(bad)} violation(s) over {read['allocations']} "
                f"allocation(s) on {read['nodes']} node(s), e.g. {bad[:3]}: "
                "every allocation of a group that asks ports holds exactly "
                "the ports it asked, each inside its node's dynamic range "
                "and outside its reserved ports, and no value twice on a "
                "node (the configuration's guarantees)")


def deploy(config: dict, seed: int, toy: bool) -> Deployment:
    return Deployment(config, seed, toy)
