"""The solver service's usage carry hears of a committed free.

The carry is the store's usage at the last resync plus every solve
since (tensor/solver.py). A job's deregistration frees its nodes in the
store and in the incremental feed; the feed counts the negative deltas
it folds (`IncrementalFeed.free_epoch`) and a dispatch whose carry was
rebuilt at another count resyncs first (`stats["stale_frees"]`). Also
here: the `solver.idle` span of the service's parked thread."""

import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.obs import TRACER
from nomad_tpu.obs.trace import R_NAME, R_T0, R_T1, R_THREAD
from nomad_tpu.structs import enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.tensor.solver import get_service

NODES, JOBS, COUNT, ASK = 64, 6, 300, 400
# 14000 MHz / 400 MHz = 35 tasks a node, 2,240 the fleet: one backlog
# of 6 x 300 takes 80% of it, so a second one fits only on freed nodes
FITS = NODES * (14000 // ASK)


def _node():
    node = mock.node()
    node.resources.cpu, node.resources.memory_mb = 14000, 32000
    node.compute_class()
    return node


def _backlog(tag: str) -> list:
    """Six service jobs the count solve takes (256 or more placements
    of one group, no spread, no port)."""
    jobs = []
    for i in range(JOBS):
        job = mock.service_job(COUNT, cpu=ASK, mem=ASK)
        job.id = job.name = f"{tag}-{i}"
        jobs.append(job)
    return jobs


@pytest.fixture
def server():
    s = Server(ServerConfig(
        num_workers=4, heartbeat_ttl=3600, gc_interval=3600,
        sched_config=SchedulerConfiguration(
            scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)))
    s.start()
    try:
        for _ in range(NODES):
            s.register_node(_node())
        yield s
    finally:
        s.stop()


def _drain(s, jobs) -> dict:
    """Register, wait, -> what the service counted meanwhile."""
    before = dict(get_service().stats)
    for job in jobs:
        s.register_job(job)
    assert s.wait_for_idle(120.0)
    after = get_service().stats
    return {k: after[k] - before[k]
            for k in ("solves", "resyncs", "stale_frees", "rejections")}


def _placed(s, jobs) -> list:
    snap = s.store.snapshot()
    return [sum(1 for a in snap.allocs_by_job(j.id)
                if not a.terminal_status()) for j in jobs]


def _blocked(s, jobs) -> list:
    snap = s.store.snapshot()
    return [ev.id for j in jobs for ev in snap.evals_by_job(j.id)
            if ev.status == enums.EVAL_STATUS_BLOCKED]


def test_a_backlog_onto_a_purged_cluster_drains_after_one_resync(server):
    """Fails on the parent (3cb0eeb): the second backlog is solved
    against a carry that still holds the first, so the fleet reads 80%
    full, about a job and a half of six is placed and the rest block
    with nothing left to unblock them (4 of 6 jobs at the benchmark's
    --toy size: PERF.md, PR 35)."""
    assert 2 * JOBS * COUNT > FITS >= JOBS * COUNT
    first = _backlog("first")
    assert _drain(server, first)["solves"] == JOBS
    assert _placed(server, first) == [COUNT] * JOBS
    registry = REGISTRY.dump().get("nomad.solver.stale_frees", 0)

    for job in first:
        server.deregister_job(job.id, purge=True)
    assert server.wait_for_idle(120.0)
    assert sum(_placed(server, first)) == 0

    second = _backlog("second")
    counted = _drain(server, second)
    assert _placed(server, second) == [COUNT] * JOBS
    assert _blocked(server, second) == []
    # one resync for the whole purge of 1,800 allocations, not one a row
    assert counted["stale_frees"] == 1 and counted["resyncs"] == 1
    assert counted["rejections"] == 0
    assert REGISTRY.dump()["nomad.solver.stale_frees"] == registry + 1


def test_a_backlog_with_no_free_before_it_forces_no_resync(server):
    """Two backlogs that fit side by side (half the asks each): the
    second chains on the first's carry; nothing was freed, so nothing
    is owed."""
    half = []
    for tag in ("a", "b"):
        jobs = _backlog(tag)
        for job in jobs:
            job.task_groups[0].tasks[0].resources.cpu = ASK // 2
            job.task_groups[0].tasks[0].resources.memory_mb = ASK // 2
        half.append(jobs)
    first = _drain(server, half[0])
    assert first["stale_frees"] == 0
    second = _drain(server, half[1])
    assert second["solves"] == JOBS
    assert second["stale_frees"] == 0 and second["resyncs"] == 0
    assert _placed(server, half[0] + half[1]) == [COUNT] * (2 * JOBS)


def test_the_parked_service_thread_holds_a_solver_idle_span(server):
    TRACER.set_enabled(True)
    TRACER.clear()
    jobs = _backlog("warm")[:1]
    _drain(server, jobs)
    t_parked = time.time()
    time.sleep(0.2)
    # the next request ends the span the thread has been parked in
    _drain(server, _backlog("wake")[:1])
    spans = TRACER.spans()
    service = next(t for t in threading.enumerate()
                   if t.name == "bulk-solver")
    idle = [r for r in spans if r[R_NAME] == "solver.idle"]
    assert idle and {r[R_THREAD] for r in idle} == {service.name}
    dispatch = [r for r in spans if r[R_NAME] == "solver.dispatch"]
    assert {r[R_THREAD] for r in dispatch} == {service.name}
    # one of them covers the stretch in which nothing was asked ...
    assert any(r[R_T0] <= t_parked and r[R_T1] >= t_parked + 0.2
               for r in idle)
    # ... and none overlaps a dispatch: parked means nothing in flight
    for r in idle:
        assert not any(d[R_T0] < r[R_T1] and d[R_T1] > r[R_T0]
                       for d in dispatch)
