"""Deploy kind `single_agent_live`: `single_agent` held to the liveness
guarantee its configuration states.

"A job submitted to a cluster with room for all of it is placed":
every time the harness quiesces the deployment (a round's end, outside
the window), the store is read once more, and a run in which an
evaluation of a live job sits `blocked` although what its job misses
would fit ends there with an exception: the harness prints the
traceback and exits with code 1 and no result line, as for any
exception in a run. A blocked job is not a failed one, so `correct`
alone would pass such a run and count the stalled round as a slow one.
A blocked evaluation of a job that is whole counts too: it waits for
capacity its job no longer needs, nothing will ever unblock it, and the
client that follows the job's evaluations never sees them complete
(the tree before PR 36 leaves such evaluations behind: an evaluation
that comes up short against the stale carry blocks a follow-up at once
and, where a later attempt of its own places the rest, leaves it).

Room is counted as the configuration counts it: `allocations_that_fit`
tasks of the cell's ask (`toy.allocations_that_fit` at --toy size,
where fleet and ask are the toy's). The warm-up's jobs ask 1 MHz / 1 MB
a task and take none of those slots; they are left out by id."""

from __future__ import annotations

from benchmark.deploy import single_agent


class NotLive(RuntimeError):
    """The liveness guarantee does not hold in this run."""


class Deployment(single_agent.Deployment):
    def __init__(self, config: dict, seed: int, toy: bool):
        super().__init__(config, seed, toy)
        sized = config["toy"] if toy else config
        self.fits = int(sized["allocations_that_fit"])
        self.warm_ids: set = set()

    def warm(self, warm_specs: dict, scatter_buckets: bool) -> dict:
        self.warm_ids = {s["id"] for group in warm_specs.values()
                         for s in group}
        return super().warm(warm_specs, scatter_buckets)

    def quiesce(self, timeout: float = 10.0) -> bool:
        quiet = super().quiesce(timeout)
        self.hold_liveness()
        return quiet

    def hold_liveness(self) -> None:
        """Raise NotLive if a live job's evaluation is blocked while
        the cluster has room for what the blocked jobs still miss."""
        from benchmark.check import live_count

        snap = self.server.store.snapshot()
        try:
            jobs = {(j.namespace, j.id): j for j in snap.jobs()
                    if not j.stop and j.id not in self.warm_ids}
            blocked = [ev for ev in snap.evals()
                       if ev.status == "blocked"
                       and (ev.namespace, ev.job_id) in jobs]
            if not blocked:
                return
            live = {key: live_count(snap, key[1], key[0]) for key in jobs}
        finally:
            snap.close()
        # what the blocked jobs still miss; a blocked evaluation of a job
        # that is whole waits for nothing at all, and is no better
        short = {key: max(0, sum(tg.count for tg in jobs[key].task_groups)
                          - live[key]) for key in
                 {(ev.namespace, ev.job_id) for ev in blocked}}
        placed, missing = sum(live.values()), sum(short.values())
        if placed + missing <= self.fits:
            whole = sum(1 for n in short.values() if n == 0)
            raise NotLive(
                f"liveness: {len(blocked)} blocked evaluation(s) of "
                f"{len(short)} live job(s), {whole} of them whole, that "
                f"miss {missing} placement(s), with {placed} live "
                f"allocation(s) and room for {self.fits - placed} more of "
                f"the {self.fits} that fit: a job submitted to a cluster "
                "with room for all of it is placed (the configuration's "
                "guarantees)")


def deploy(config: dict, seed: int, toy: bool) -> Deployment:
    return Deployment(config, seed, toy)
