"""The count solve, as the served path calls it, against the plain
reference the benchmark's `correct` compares it with.

Jobs go in through `Server.register_job`, a worker's placer hands them
to `BulkSolverService.solve`, the plan applier commits the AllocBlock;
the reference is `benchmark/reference/binpack_counts.py` (greedy BestFit
in counts form on one usage array, numpy only, sharing no code with
nomad_tpu/tensor/). One job solved alone fills the same nodes to the
same counts; a burst of jobs in ONE launch places every job whole, puts
no node over its capacity and packs as well as the reference within the
cell's own `fitness_rel_tol`.

Tolerances. The counts are compared exactly, but node for node only up
to the order among nodes that score the same: the kernel breaks such
ties by a seeded jitter of 3e-5 (kernels.TIE_JITTER), the reference by
node number, so nodes are compared as the sorted list of (capacity,
usage before, count). The fleets keep distinct shapes' scores apart by
far more than the jitter and than f32 rounding (tier-1 runs x64 on, the
entry points x64 off; the service ships f32 either way). The fitness
is a mean of f64 scores of integral usages and is held to the cell's
own limit, 0.005 (`traffic/plain.300.json`). A burst's jobs all ask the
same, as the cell's do: the launch takes jobs as they arrive and the
reference as they were submitted, and with asks that differ the order
alone moved the fitness by 1.6-5.3% on the mixed fleet (four jobs of
1000/1000, 1500/800, 700/2000 MHz/MB, six runs), which is the
reference's own spread over orders and no fault of the solve."""

import json
import random
import threading
from pathlib import Path

import numpy as np
import pytest

from benchmark import check
from benchmark.reference import binpack_counts
from benchmark.reference.fitness import mean_fitness
from nomad_tpu import mock
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.structs import enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.tensor import solver as solver_mod

ROOT = Path(__file__).resolve().parents[1]
FITNESS_REL_TOL = json.loads(
    (ROOT / "benchmark/traffic/plain.300.json").read_text()
)["check"]["fitness_rel_tol"]
COUNT = 300     # the cell's job; 256 or more go to the count solve


def _uniform(n: int, seed: int) -> list:
    """The grid's node: 14000 MHz / 32000 MB, two of its tasks each."""
    nodes = []
    for _ in range(n):
        node = mock.node()
        node.resources.cpu, node.resources.memory_mb = 14000, 32000
        node.compute_class()
        nodes.append(node)
    return nodes


def _mixed(n: int, seed: int) -> list:
    """The C2M ladder's mix, drawn from the seed: {8, 16, 32}K MHz x
    {16, 32, 64} GB."""
    rng = random.Random(seed)
    nodes = []
    for i in range(n):
        node = mock.node()
        mock.shape_node(node, i, rng)
        nodes.append(node)
    return nodes


FLEETS = {
    # name: (builder, nodes, the asks (cpu MHz, mem MB) of a burst)
    "uniform": (_uniform, 512, [(6000, 6000)] * 3),
    "mixed": (_mixed, 160, [(1000, 1000)] * 4),
}


def _job(tag: str, ask: tuple):
    job = mock.service_job(COUNT, cpu=ask[0], mem=ask[1])
    job.id = job.name = tag
    return job


@pytest.fixture
def served(request, monkeypatch):
    """A server over the fleet named by the test's parameter, with a
    solver service of its own -> (server, service, the burst's asks)."""
    build, n, asks = FLEETS[request.param]
    service = solver_mod.BulkSolverService()
    monkeypatch.setattr(solver_mod, "_service", service)
    s = Server(ServerConfig(
        num_workers=4, heartbeat_ttl=3600, gc_interval=3600,
        sched_config=SchedulerConfiguration(
            scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)))
    s.start()
    try:
        for node in build(n, seed=11):
            s.register_node(node)
        yield s, service, asks
    finally:
        s.stop()
        service.stop()


def _arrays(s) -> dict:
    return check.cluster_arrays(s.store.snapshot(), "")


def _counts(s, fleet: dict, job_ids) -> np.ndarray:
    index_of = {nid: i for i, nid in enumerate(fleet["ids"])}
    return check.placements_per_node(s.store.snapshot(), set(job_ids),
                                     index_of)[0]


def _by_shape(cap, used, counts) -> list:
    return sorted(zip(map(tuple, cap), map(tuple, used), counts.tolist()))


@pytest.mark.parametrize("served", sorted(FLEETS), indirect=True)
def test_one_job_alone_fills_the_nodes_the_reference_fills(served):
    s, service, asks = served
    for step, ask in enumerate([asks[0], (asks[0][0] // 2, asks[0][1])]):
        before = _arrays(s)
        job = _job(f"alone-{step}", ask)
        s.register_job(job)
        assert s.wait_for_idle(120.0)
        got = _counts(s, before, [job.id])
        used = before["used"].copy()
        want = binpack_counts.place_job(
            before["cap"], used, np.array(ask, np.float64), COUNT)
        assert got.sum() == want.sum() == COUNT
        # the second job lands on the usage the first left, through the
        # chained carry: no resync in between
        assert _by_shape(before["cap"], before["used"], got) == \
            _by_shape(before["cap"], before["used"], want)
    assert service.stats["solves"] == 2 and service.stats["resyncs"] == 1
    assert service.stats["rejections"] == 0


@pytest.mark.parametrize("served", sorted(FLEETS), indirect=True)
def test_a_burst_in_one_launch_packs_as_the_reference(served, monkeypatch):
    s, service, asks = served
    # hold the service's thread back until the whole burst is queued:
    # the launch then takes every job at once, up to G_PAD = 16
    start = service._ensure_thread
    monkeypatch.setattr(
        service, "_ensure_thread",
        lambda: start() if service._q.qsize() >= len(asks) else None)
    fallback = threading.Timer(30.0, start)
    fallback.start()
    before = _arrays(s)
    jobs = [_job(f"burst-{i}", ask) for i, ask in enumerate(asks)]
    try:
        for job in jobs:
            s.register_job(job)
        assert s.wait_for_idle(120.0)
    finally:
        fallback.cancel()
    assert service.stats["launches"] == 1
    assert service.stats["solves"] == len(jobs)
    assert service.stats["rejections"] == 0

    snap = s.store.snapshot()
    assert [check.live_count(snap, j.id) for j in jobs] == [COUNT] * len(jobs)
    after = _arrays(s)
    assert not np.any(after["used"] > after["cap"] + 1e-6)
    got = _counts(s, before, [j.id for j in jobs])
    fitness = mean_fitness(after["cap"], after["used"], got)

    used = before["used"].copy()
    want = np.zeros(len(used), np.int64)
    for ask in asks:
        want += binpack_counts.place_job(
            before["cap"], used, np.array(ask, np.float64), COUNT)
    assert want.sum() == got.sum() == COUNT * len(jobs)
    reference = mean_fitness(before["cap"], used, want)
    assert fitness >= reference * (1.0 - FITNESS_REL_TOL), (
        fitness, reference)
    # equal asks: the chain inside the launch is the reference's
    # sequence whatever the order of arrival
    assert _by_shape(before["cap"], before["used"], got) == \
        _by_shape(before["cap"], before["used"], want)
