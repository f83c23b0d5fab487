"""The system scheduler's device path (TPUPlacer.place_system,
kernels.system_place) against the exact host path (NodeScorer +
scheduler/preemption.py) and the plain reference
(benchmark/reference/preemption.py): placement per node and victim
sets exactly equal; the kernel against its numpy mirror; and the plan
applier's preemption evaluations."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark.reference import preemption as reference
from nomad_tpu import mock
from nomad_tpu.state import StateStore
from nomad_tpu.structs import enums
from nomad_tpu.structs.job import MigrateStrategy
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.resources import NetworkResource
from nomad_tpu.testing import Harness

N_NODES = 48          # over the placer's HOST_CUTOVER: the device path
CPU, MEM = 4000, 8192

# (priority, cpu, mem, max_parallel): three preemptible levels for a
# job at 100, one within 10 of it, mixed sizes inside a level
SHAPES = [(20, 1000, 512, 0), (20, 250, 2048, 0), (40, 500, 256, 0),
          (40, 1500, 1024, 0), (40, 2000, 256, 0), (85, 750, 512, 0),
          (95, 1000, 1024, 0)]
COUPLED = [(40, 500, 256, 1), (40, 1500, 512, 2)]


def _cluster(seed: int, shapes, room_every: int = 7, stuck_every: int = 11):
    """A store full of the shapes' allocations on N_NODES nodes, every
    `room_every`-th node left with room, every `stuck_every`-th full of
    priority-95 work that cannot be preempted. Ids are seeded, so two
    builds with one seed are the same cluster."""
    rng = np.random.default_rng(seed)
    store = StateStore()
    nodes = []
    for i in range(N_NODES):
        node = mock.node()
        node.id = node.name = f"node-{i:03d}"
        node.resources.cpu, node.resources.memory_mb = CPU, MEM
        node.compute_class()
        store.upsert_node(node)
        nodes.append(node)
    jobs = []
    for k, (prio, cpu, mem, mp) in enumerate(shapes):
        job = mock.batch_job()
        job.id = job.name = f"job-{k}-p{prio}"
        job.priority = prio
        tg = job.task_groups[0]
        tg.tasks[0].resources.cpu, tg.tasks[0].resources.memory_mb = cpu, mem
        if mp:
            tg.migrate = MigrateStrategy(max_parallel=mp)
        store.upsert_job(job)
        jobs.append(job)
    stuck = next(j for j in jobs if j.priority == 95) if any(
        j.priority == 95 for j in jobs) else None
    allocs = []
    for i, node in enumerate(nodes):
        used = np.zeros(2)
        pool = [stuck] if (stuck is not None and i % stuck_every == 3) else jobs
        budget = CPU - (1200 if i % room_every == 0 else 0)
        for _ in range(40):
            job = pool[int(rng.integers(len(pool)))]
            res = job.task_groups[0].tasks[0].resources
            if used[0] + res.cpu > budget or used[1] + res.memory_mb > MEM:
                continue
            a = mock.alloc(job, node)
            a.id = f"{int(rng.integers(1 << 30)):08x}-{len(allocs):05d}"
            a.job = job
            a.allocated_vec = job.task_groups[0].combined_resources().vec()
            a.client_status = enums.ALLOC_CLIENT_RUNNING
            allocs.append(a)
            used += (res.cpu, res.memory_mb)
    store.upsert_allocs(allocs)
    return store


def _system_job(ports: bool = False):
    job = mock.system_job()
    job.id = job.name = "sys"
    job.task_groups[0].tasks[0].resources.cpu = 700
    job.task_groups[0].tasks[0].resources.memory_mb = 300
    if ports:
        job.task_groups[0].tasks[0].resources.networks = [
            NetworkResource(dynamic_ports=["http"])]
    return job


def _run(seed, shapes, algorithm, ports=False):
    store = _cluster(seed, shapes)
    job = _system_job(ports)
    store.upsert_job(job)
    h = Harness(store)
    h.process(mock.eval_for(job),
              sched_config=SchedulerConfiguration(
                  scheduler_algorithm=algorithm))
    plan = h.plans[-1]
    placed, ports_of, victims = set(), {}, {}
    for nid, allocs in plan.node_allocation.items():
        for a in allocs:
            placed.add(nid)
            ports_of[nid] = [p.value for p in a.allocated_ports]
    for block in plan.alloc_blocks:
        for q, nid in enumerate(block.node_ids):
            placed.add(nid)
            ports_of[nid] = ([] if block.ports is None
                             else block.ports.values[q].tolist())
    for nid, allocs in plan.node_preemptions.items():
        victims.setdefault(nid, set()).update(a.id for a in allocs)
    if plan.preempted:
        for aid, nid in zip(plan.preempted.alloc_ids,
                            plan.preempted.node_ids):
            victims.setdefault(nid, set()).add(aid)
    return store, plan, placed, victims, ports_of


def _reference(store, job):
    """The plain reference on the store's state before the plan."""
    snap = store.snapshot()
    nodes = snap.ready_nodes_in_pool(job.datacenters, job.node_pool)
    ask = job.task_groups[0].combined_resources().vec()
    counts: dict = {}
    placed, victims = set(), {}
    for node in nodes:
        allocs = [{"id": a.id, "priority": a.job.priority,
                   "vec": a.allocated_vec,
                   "max_parallel": (a.job.task_groups[0].migrate.max_parallel
                                    if a.job.task_groups[0].migrate else 0),
                   "group": (a.namespace, a.job_id, a.task_group)}
                  for a in snap.allocs_by_node(node.id)
                  if not a.terminal_status()]
        got = reference.choose(node.available_vec(), allocs, ask,
                               job.priority, counts)
        if got is None:
            continue
        placed.add(node.id)
        if got:
            victims[node.id] = set(got)
    return placed, victims


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("case", ["levels", "max_parallel"])
def test_device_path_equals_host_and_reference(seed, case):
    from nomad_tpu.tensor.placer import preempt_stats

    shapes = SHAPES + (COUPLED if case == "max_parallel" else [])
    host = _run(seed, shapes, enums.SCHED_ALG_BINPACK)
    before = preempt_stats()
    device = _run(seed, shapes, enums.SCHED_ALG_TPU_BINPACK)
    after = preempt_stats()
    _, plan, placed, victims, _ = device
    assert plan.alloc_blocks, "the device path handed over no block"
    assert placed == host[2]
    assert victims == host[3]
    ref_placed, ref_victims = _reference(_cluster(seed, shapes), _system_job())
    assert placed == ref_placed
    assert victims == ref_victims
    # the scenario covers what it claims: nodes with room, nodes that
    # cannot be freed, preempting nodes
    assert 0 < len(placed) < N_NODES
    assert any(nid not in victims for nid in placed)
    assert len(victims) > 5
    fallback = after["host_fallback_nodes"] - before["host_fallback_nodes"]
    if case == "levels":
        assert fallback == 0
        assert plan.preempted is not None and not plan.node_preemptions


def test_distance_decides_within_a_group():
    """Equal priority, one allocation the exact size of what is missing
    and one far larger: the exact one goes, on every node."""
    shapes = [(50, 700, 300, 0), (50, 2500, 2000, 0)]
    _, plan, placed, victims, _ = _run(5, shapes, enums.SCHED_ALG_TPU_BINPACK)
    store = _cluster(5, shapes)
    snap = store.snapshot()
    assert victims
    for nid, ids in victims.items():
        sizes = {snap.alloc_by_id(aid).allocated_vec[0] for aid in ids}
        assert sizes == {700}


def _superset_cluster():
    """Every node full with a priority-20 allocation of cpu alone and a
    priority-40 one of cpu and memory, and a system job that needs cpu
    and memory freed."""
    store = StateStore()
    nodes = []
    for i in range(N_NODES):
        node = mock.node()
        node.id = node.name = f"node-{i:03d}"
        node.resources.cpu, node.resources.memory_mb = 2000, 2000
        node.compute_class()
        store.upsert_node(node)
        nodes.append(node)
    jobs = {}
    for name, prio, cpu, mem in (("low", 20, 1000, 0),
                                 ("mid", 40, 1000, 1000)):
        job = mock.batch_job()
        job.id = job.name = name
        job.priority = prio
        res = job.task_groups[0].tasks[0].resources
        res.cpu, res.memory_mb = cpu, mem
        job.task_groups[0].ephemeral_disk.size_mb = 0
        store.upsert_job(job)
        jobs[name] = job
    allocs = []
    for node in nodes:
        for name, job in jobs.items():
            a = mock.alloc(job, node)
            a.id = f"{name}-{node.id}"
            a.allocated_vec = job.task_groups[0].combined_resources().vec()
            allocs.append(a)
    store.upsert_allocs(allocs)
    job = mock.system_job()
    job.id = job.name = "sys"
    res = job.task_groups[0].tasks[0].resources
    res.cpu, res.memory_mb = 1000, 1500
    job.task_groups[0].ephemeral_disk.size_mb = 0
    store.upsert_job(job)
    return store, job, nodes


@pytest.mark.parametrize("algorithm", [enums.SCHED_ALG_BINPACK,
                                       enums.SCHED_ALG_TPU_BINPACK])
def test_filter_superset_drops_the_unneeded_victim(algorithm):
    """A lower group's victim covers cpu alone; the next group's covers
    cpu and memory: the first is dropped again, by both paths."""
    store, job, nodes = _superset_cluster()
    h = Harness(store)
    h.process(mock.eval_for(job), sched_config=SchedulerConfiguration(
        scheduler_algorithm=algorithm))
    plan = h.plans[-1]
    got = ({a.id for v in plan.node_preemptions.values() for a in v}
           | set(plan.preempted.alloc_ids if plan.preempted else ()))
    assert got == {f"mid-{n.id}" for n in nodes}
    allocs = [{"id": f"{name}-x", "priority": prio, "vec": vec,
               "max_parallel": 0, "group": name}
              for name, prio, vec in (("low", 20, [1000, 0, 0, 0]),
                                      ("mid", 40, [1000, 1000, 0, 0]))]
    assert reference.choose([2000, 2000, 1e5, 1e4], allocs,
                            [1000, 1500, 0, 0], 100) == ["mid-x"]


def test_ports_of_the_block_are_the_host_paths():
    host = _run(2, SHAPES, enums.SCHED_ALG_BINPACK, ports=True)
    device = _run(2, SHAPES, enums.SCHED_ALG_TPU_BINPACK, ports=True)
    assert device[1].alloc_blocks[0].ports is not None
    assert device[2] == host[2] and device[3] == host[3]
    assert device[4] == host[4]


def _columns(rng, n, v, d=4):
    prio = rng.choice([10.0, 20.0, 40.0, 40.0, 60.0], size=(n, v))
    vec = rng.choice([0.0, 250.0, 500.0, 1000.0, 1500.0], size=(n, v, d))
    valid = rng.random((n, v)) < 0.8
    mp = rng.choice([0.0, 0.0, 1.0, 2.0], size=(n, v))
    grp = np.tile(np.arange(v, dtype=np.int32), (n, 1))
    same = rng.random((n, v)) < 0.3
    grp[:, 1:] = np.where(same[:, 1:], 0, grp[:, 1:])
    cnt0 = rng.choice([0.0, 1.0, 2.0], size=(n, v))
    return prio, vec, valid, mp, grp, cnt0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kernel_equals_its_numpy_mirror(seed):
    import jax

    from nomad_tpu.tensor.kernels import system_place, system_place_np

    rng = np.random.default_rng(seed)
    n, v, d = 64, 8, 4
    avail = rng.choice([3000.0, 4000.0], size=(n, d))
    used = rng.choice([2500.0, 3000.0, 3500.0, 4000.0], size=(n, d))
    used = np.minimum(used, avail)
    ask = np.array([700.0, 600.0, 0.0, 1.0])
    feasible = rng.random(n) < 0.9
    target = rng.random(n) < 0.9
    cols = _columns(rng, n, v, d)
    args = (avail, used, ask, feasible, target, np.bool_(True)) + cols
    want = system_place_np(*args)
    got = jax.device_get(system_place(*jax.device_put(args)))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[2], want[2], rtol=1e-6)
    assert want[1].any() and (want[0] & ~want[1].any(axis=1)).any()


def _preempting_plan(store, victims_by_node, node_ids):
    from nomad_tpu.structs.plan import Plan

    job = _system_job()
    store.upsert_job(job)
    plan = Plan(eval_id="e1", priority=job.priority, job=job)
    for nid in node_ids:
        a = mock.alloc(job, store.snapshot().node_by_id(nid))
        a.job = job
        a.allocated_vec = job.task_groups[0].combined_resources().vec()
        plan.append_alloc(a)
        for victim in victims_by_node.get(nid, ()):
            plan.append_preempted_alloc(victim, a.id)
    return plan


def test_plan_that_preempts_creates_one_evaluation_per_job():
    """A plan whose placements preempt allocations of several jobs
    commits one pending evaluation triggered by "preemption" for each
    of those jobs, and none for a job that lost nothing."""
    from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue

    store = _cluster(0, SHAPES)
    snap = store.snapshot()
    node_ids = [n.id for n in snap.nodes()][:6]
    victims = {}
    for nid in node_ids:
        live = [a for a in snap.allocs_by_node(nid)
                if a.job.priority <= 40 and not a.terminal_status()]
        victims[nid] = live[:2]
    lost = {a.job_id for v in victims.values() for a in v}
    assert len(lost) >= 2
    plan = _preempting_plan(store, victims, node_ids)
    applier = PlanApplier(store, PlanQueue())
    enqueued = []
    applier.on_evals = enqueued.extend
    result = applier.apply(plan)
    evals = [ev for ev in store.snapshot().evals()
             if ev.triggered_by == enums.TRIGGER_PREEMPTION]
    assert sorted(ev.job_id for ev in evals) == sorted(lost)
    assert all(ev.status == enums.EVAL_STATUS_PENDING for ev in evals)
    assert {ev.id for ev in enqueued} == {ev.id for ev in evals}
    assert len(result.preemption_evals) == len(lost)
    for v in victims.values():
        for a in v:
            row = store.snapshot().alloc_by_id(a.id)
            assert row.desired_status == enums.ALLOC_DESIRED_EVICT


def test_an_eval_blocked_on_a_snapshot_older_than_the_last_free_retries():
    """A preempted job's evaluation that fails on a snapshot taken before
    capacity was freed must not wait for a change that already happened
    (upstream's missedUnblock): it goes back to the broker at once; one
    blocked on a newer snapshot waits."""
    from nomad_tpu.core.blocked import BlockedEvals

    enqueued = []
    blocked = BlockedEvals(enqueued.append)
    blocked.set_enabled(True)
    assert blocked.unblock("", index=10) == 0
    stale = mock.eval_for(mock.batch_job())
    stale.status, stale.snapshot_index = enums.EVAL_STATUS_BLOCKED, 5
    blocked.block(stale)
    assert enqueued == [stale] and blocked.stats["missed_unblocks"] == 1
    fresh = mock.eval_for(mock.batch_job())
    fresh.status, fresh.snapshot_index = enums.EVAL_STATUS_BLOCKED, 12
    blocked.block(fresh)
    assert enqueued == [stale] and blocked.stats["blocked"] == 1
    assert blocked.unblock("", index=13) == 1 and enqueued[-1] is fresh


def test_an_eval_reblocked_during_its_delivery_is_retried_after_its_ack(
        monkeypatch):
    """A blocked eval retried, failing again on a snapshot older than the
    last free, goes back to the broker: after its own delivery is acked,
    not while it is unacked (the broker drops an enqueue of an eval it
    has handed out, and the eval would be lost)."""
    from concurrent.futures import Future
    from copy import copy as _copy
    from types import SimpleNamespace

    from nomad_tpu.core import worker as worker_mod
    from nomad_tpu.core.blocked import BlockedEvals
    from nomad_tpu.core.broker import EvalBroker

    broker = EvalBroker()
    broker.set_enabled(True)
    blocked = BlockedEvals(broker.enqueue)
    blocked.set_enabled(True)
    done = Future()
    done.set_result(None)
    snap = SimpleNamespace(index=20)
    server = SimpleNamespace(
        broker=broker, blocked=blocked, logger=None, sched_config=None,
        events=None, config=SimpleNamespace(nack_timeout=30.0),
        plan_applier=SimpleNamespace(
            submit_eval_updates=lambda evals: done),
        store=SimpleNamespace(snapshot_min_index=lambda index: snap))

    class Retry:
        """The blocked eval's scheduler: fails on its snapshot (5) while
        capacity is freed at 10, and reblocks itself."""

        def __init__(self, planner):
            self.planner = planner

        def process(self, ev):
            blocked.unblock("", index=10)
            again = _copy(ev)
            again.snapshot_index = 5
            self.planner.reblock_eval(again)

    monkeypatch.setattr(worker_mod, "NewScheduler",
                        lambda typ, snap, planner, **kw: Retry(planner))
    ev = mock.eval_for(mock.batch_job())
    ev.status = enums.EVAL_STATUS_BLOCKED
    broker.enqueue(ev)
    got, token = broker.dequeue([ev.type], timeout=1.0)
    assert got.id == ev.id
    owner = SimpleNamespace(server=server, _sched_caches={},
                            _count=lambda key: None)
    worker_mod._EvalRun(owner, got, token, snapshot=snap).run()
    again, _ = broker.dequeue([ev.type], timeout=1.0)
    assert again is not None and again.id == ev.id
    assert blocked.stats["missed_unblocks"] == 1


def test_column_evictions_verify_as_arrays_and_commit():
    """The device path's plan (a block and its evictions as columns)
    through the applier: the nodes get an array verdict, every victim
    is evicted, and every job that lost one has its evaluation."""
    from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue

    store = _cluster(1, SHAPES)
    job = _system_job()
    store.upsert_job(job)

    class Planner(Harness):
        def submit_plan(self, plan):
            self.plans.append(plan)
            return self.applier.apply(plan), None

    h = Planner(store)
    h.applier = PlanApplier(store, PlanQueue())
    h.process(mock.eval_for(job), sched_config=SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK))
    plan = h.plans[-1]
    assert plan.preempted and plan.alloc_blocks
    stats = h.applier.stats
    assert stats["nodes_verified_columnar"] == stats["nodes_verified"] > 0
    snap = store.snapshot()
    for aid in plan.preempted.alloc_ids:
        assert snap.alloc_by_id(aid).desired_status == \
            enums.ALLOC_DESIRED_EVICT
    lost = {g[1] for g in plan.preempted.groups}
    evals = {ev.job_id for ev in snap.evals()
             if ev.triggered_by == enums.TRIGGER_PREEMPTION}
    assert evals == lost
    for node in snap.nodes():
        used = sum((a.allocated_vec for a in snap.allocs_by_node(node.id)
                    if not a.terminal_status()), np.zeros(4))
        assert (used <= node.available_vec()).all()


def _block_cluster():
    """Every node full of block positions at priority 50: two of a
    service job's 1500 MHz and two of a batch job's 500 MHz, one
    AllocBlock a job."""
    from nomad_tpu.structs.alloc import AllocBlock

    store = StateStore()
    nodes = []
    for i in range(N_NODES):
        node = mock.node()
        node.id = node.name = f"node-{i:03d}"
        node.resources.cpu, node.resources.memory_mb = CPU, MEM
        node.compute_class()
        store.upsert_node(node)
        nodes.append(node)
    for name, cpu in (("svc", 1500), ("fill", 500)):
        job = mock.batch_job()
        job.id = job.name = name
        job.priority = 50
        tg = job.task_groups[0]
        tg.tasks[0].resources.cpu, tg.tasks[0].resources.memory_mb = cpu, 256
        tg.ephemeral_disk.size_mb = 0
        store.upsert_job(job)
        store.upsert_plan_results([], alloc_blocks=[AllocBlock(
            id=f"blk-{name}", eval_id=f"ev-{name}", namespace=job.namespace,
            job_id=job.id, job=job, job_version=job.version,
            task_group=tg.name,
            name_indices=np.arange(2 * N_NODES, dtype=np.int64),
            node_ids=[n.id for n in nodes], node_names=[n.name for n in nodes],
            counts=np.full(N_NODES, 2, dtype=np.int64),
            allocated_vec=tg.combined_resources().vec())], job=job)
    job = _system_job()
    job.task_groups[0].ephemeral_disk.size_mb = 0
    store.upsert_job(job)
    return store, job


def test_block_positions_as_victims_are_evicted_without_materialising():
    """Victims that are block positions: read from the blocks' columns,
    chosen as the host path and the reference choose them (the two
    500 MHz positions, never a 1500 MHz one), verified as arrays, and
    promoted to evicted rows only by the store's commit."""
    from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue
    from nomad_tpu.structs.alloc import AllocBlock

    host_store, job = _block_cluster()
    host = Harness(host_store)
    host.process(mock.eval_for(job), sched_config=SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_BINPACK))
    want = {nid: {a.id for a in allocs}
            for nid, allocs in host.plans[-1].node_preemptions.items()}

    store, job = _block_cluster()

    class Planner(Harness):
        def submit_plan(self, plan):
            self.plans.append(plan)
            return self.applier.apply(plan), None

    h = Planner(store)
    h.applier = PlanApplier(store, PlanQueue())
    materialised = []
    real = AllocBlock.alloc_at
    AllocBlock.alloc_at = lambda self, p: (materialised.append(p),
                                           real(self, p))[1]
    try:
        h.process(mock.eval_for(job), sched_config=SchedulerConfiguration(
            scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK))
        planned = len(materialised)
    finally:
        AllocBlock.alloc_at = real
    plan = h.plans[-1]
    got: dict = {}
    for aid, nid in zip(plan.preempted.alloc_ids, plan.preempted.node_ids):
        got.setdefault(nid, set()).add(aid)
    assert got == want and len(got) == N_NODES
    assert all(aid.startswith("blk-fill.") for ids in got.values()
               for aid in ids)
    # nothing was materialised to choose or verify the victims; the
    # commit promotes each victim once, to its evicted row
    assert planned == len(plan.preempted)
    stats = h.applier.stats
    assert stats["nodes_verified_columnar"] == stats["nodes_verified"]
    snap = store.snapshot()
    assert not snap.live_mask(plan.preempted.alloc_ids).any()
    for aid in plan.preempted.alloc_ids:
        assert snap.alloc_by_id(aid).desired_status == \
            enums.ALLOC_DESIRED_EVICT
    assert [ev.job_id for ev in snap.evals()
            if ev.triggered_by == enums.TRIGGER_PREEMPTION] == ["fill"]
