"""Evaluations in flight on one server never hand out the same port on
a node: the per-placement tier chooses a group's ports under its solve
lock and registers them in the in-flight overlay beside the usage they
belong to; every evaluation reads the ports taken from one place
(`EvalContext.port_index`: reserved, its snapshot's and its plan's
rows, the overlay's open entries and those committed since its
snapshot); the plan applier stays the gate and counts what it catches.

The racing cases fail on the tree before PR 38 (every racer takes the
lowest free port of its own snapshot on the same half-filled nodes and
the applier throws the rows away); `blind` plants that tree's view (the
overlay's ports unread) and sees the collisions come back."""

import threading
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.reference import ports as reference
from benchmark.reference.fitness import mean_fitness
from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.obs import TRACER
from nomad_tpu.obs.trace import R_ARGS, R_ID, R_NAME, R_PARENT, R_T0
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import PlacementRequest
from nomad_tpu.scheduler.scheduler import NewScheduler
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Spread, enums
from nomad_tpu.structs.alloc import AllocatedPort
from nomad_tpu.structs.network import NetworkIndex
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.plan import Plan
from nomad_tpu.structs.resources import NetworkResource
from nomad_tpu.tensor import overlay as overlay_mod
from nomad_tpu.tensor.overlay import INFLIGHT, InflightOverlay
from nomad_tpu.tensor.placer import TPUPlacer
from nomad_tpu.testing import Harness

PORT_COUNTERS = ("nomad.placer.ports_assigned", "nomad.placer.port_nodes",
                 "nomad.placer.port_nodes_inflight")


def tpu_config():
    return SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)


def grid_node(i: int, racks: int = 4):
    """upstream's grid node: two of the grid's tasks fit it."""
    node = mock.node()
    node.meta["rack"] = f"r{i % racks}"
    node.resources.cpu, node.resources.memory_mb = 14000, 32000
    node.compute_class()
    return node


def grid_job(count: int, ports: int = 2, spread: bool = True):
    """The grid's task (6000 MHz / 6000 MB), over HOST_CUTOVER so the
    scan places it, with `ports` dynamic ports and the rack spread."""
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb = 6000, 6000
    res.networks = ([NetworkResource(
        dynamic_ports=[f"p{i}" for i in range(ports)])] if ports else [])
    if spread:
        tg.spreads = [Spread(attribute="${meta.rack}", weight=50)]
    return job


@pytest.fixture(autouse=True)
def clean_overlay():
    """The overlay is the process's: no test reads another's entries."""
    def wipe():
        with INFLIGHT._lock:
            INFLIGHT._entries.clear()
            INFLIGHT._ports.clear()
            INFLIGHT._closed.clear()
    wipe()
    yield
    wipe()


def plain_state(snap):
    """(nodes, allocs) of a snapshot as benchmark/reference/ports takes
    them (the deploy kind's own conversion, benchmark/deploy/
    single_agent_ports.py)."""
    from benchmark.deploy.single_agent_ports import plain_allocs, plain_nodes

    return plain_nodes(snap), plain_allocs(snap)


class GatedPlanner(Harness):
    """A planner whose plans go through a real PlanApplier, one at a
    time; with `racers` > 1 nobody's first plan is applied before every
    racer has solved and handed its own in."""

    def __init__(self, store, racers: int = 1):
        super().__init__(store)
        self.applier = PlanApplier(store, PlanQueue())
        self.gate = threading.Barrier(racers) if racers > 1 else None
        self.results: list = []
        self._seen = threading.local()

    def submit_plan(self, plan):
        if self.gate is not None and not getattr(self._seen, "first", False):
            self._seen.first = True
            self.gate.wait(60.0)
        with self._lock:
            self.plans.append(plan)
            result = self.applier.apply(plan)
            self.results.append(result)
        if result.rejected_nodes:
            return result, self.store.snapshot()
        return result, None


def race(store, jobs, snapshot=None):
    """Every job's evaluation on a thread of its own, all at ONE
    snapshot, no plan applied before all have solved -> the planner."""
    planner = GatedPlanner(store, racers=len(jobs))
    snap = snapshot if snapshot is not None else store.snapshot()
    errors: list = []

    def one(job):
        try:
            NewScheduler("service", snap, planner,
                         sched_config=tpu_config()).process(mock.eval_for(job))
        except Exception as e:           # pragma: no cover - shown below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(j,)) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120.0)
    assert not errors, errors
    return planner


def store_with(nodes: int, jobs: list):
    store = StateStore()
    store.upsert_nodes([grid_node(i) for i in range(nodes)])
    for job in jobs:
        store.upsert_job(job)
    return store


def live(snap, job):
    return [a for a in snap.allocs_by_job(job.id) if not a.terminal_status()]


# -- the race ----------------------------------------------------------------

@pytest.mark.parametrize("racers", [2, 8])
def test_racing_evaluations_hand_out_no_port_twice(racers):
    jobs = [grid_job(24) for _ in range(racers)]
    store = store_with(16 * racers, jobs)
    planner = race(store, jobs)
    stats = planner.applier.stats
    # no row lost at the applier, every job whole at its first plan
    assert stats["nodes_rejected"] == 0 and stats["port_collisions"] == 0
    assert len(planner.plans) == racers
    snap = store.snapshot()
    assert [len(live(snap, j)) for j in jobs] == [24] * racers
    nodes, allocs = plain_state(snap)
    assert reference.violations(nodes, allocs) == []
    assert reference.census(nodes, allocs)["ports"] == 48 * racers
    # ... and they did meet on half-filled nodes: some node holds two
    # jobs' allocations, with four different ports
    shared = [n for n in nodes
              if len({a.job_id for a in snap.allocs_by_node(n)}) > 1]
    assert shared
    for node_id in shared:
        held = [p.value for a in snap.allocs_by_node(node_id)
                for p in a.allocated_ports]
        assert len(held) == len(set(held)) == 4


def test_blind_to_the_overlays_ports_the_racers_collide(monkeypatch):
    """The control: the parent's view planted (nobody reads the
    overlay's ports). The applier catches every collision and counts
    it, and the racers pay with rejected rows and further plans."""
    monkeypatch.setattr(InflightOverlay, "ports_on",
                        lambda self, node_ids, snapshot_index: {})
    jobs = [grid_job(24) for _ in range(4)]
    store = store_with(64, jobs)
    before = REGISTRY.get("nomad.plan.port_collisions")
    planner = race(store, jobs)
    stats = planner.applier.stats
    assert stats["port_collisions"] > 0
    assert stats["nodes_rejected"] >= stats["port_collisions"]
    assert (REGISTRY.get("nomad.plan.port_collisions") - before
            == stats["port_collisions"])
    assert len(planner.plans) > 4
    # the gate held: whatever was committed is sound
    nodes, allocs = plain_state(store.snapshot())
    assert reference.violations(nodes, allocs) == []


def test_a_commit_between_snapshot_and_gather_is_still_seen():
    """Motivation point 3: B's snapshot predates A's commit, B's usage
    gather does not (it is live), so B packs onto A's half-filled nodes
    and has to find A's ports in the overlay: A's entry is closed but
    stamped past B's snapshot."""
    a, b = grid_job(24), grid_job(24)
    store = store_with(32, [a, b])
    old = store.snapshot()
    first = GatedPlanner(store)
    NewScheduler("service", store.snapshot(), first,
                 sched_config=tpu_config()).process(mock.eval_for(a))
    assert len(live(store.snapshot(), a)) == 24
    assert store.latest_index > old.index
    assert not INFLIGHT._entries           # closed: its usage is committed
    second = race(store, [b], snapshot=old)
    assert second.applier.stats["nodes_rejected"] == 0
    assert second.applier.stats["port_collisions"] == 0
    snap = store.snapshot()
    assert len(live(snap, b)) == 24
    assert reference.violations(*plain_state(snap)) == []
    assert any(len({x.job_id for x in snap.allocs_by_node(n.id)}) > 1
               for n in snap.nodes())


# -- an entry's life ---------------------------------------------------------

def fake_cluster(store, node_ids):
    nodes = [SimpleNamespace(id=nid) for nid in node_ids]
    return SimpleNamespace(nodes=nodes, _store=store, node_index={
        nid: i for i, nid in enumerate(node_ids)})


def registered(overlay, store, ports: dict):
    """One entry holding `ports` ({node id: [numbers]}) -> its plan."""
    plan = Plan()
    cluster = fake_cluster(store, list(ports))
    overlay.register(cluster, np.arange(len(ports)),
                     np.ones((len(ports), 4)), plan, ports)
    return plan


def close(plan, rejected=()):
    for hook in plan.post_apply_hooks:
        hook(SimpleNamespace(rejected_nodes=list(rejected)))


def test_open_entries_are_read_by_everyone_their_owner_included():
    overlay, store = InflightOverlay(), StateStore()
    registered(overlay, store, {"n1": [20000, 20001], "n2": [20000]})
    assert overlay.ports_on(["n1", "n2", "n3"], store.latest_index) == {
        "n1": {20000, 20001}, "n2": {20000}}
    assert overlay.ports_on(["n3"], 0) == {}


def test_a_rejected_nodes_ports_die_with_the_entry():
    overlay, store = InflightOverlay(), StateStore()
    old = store.snapshot()
    plan = registered(overlay, store, {"n1": [20000], "n2": [20001]})
    store.upsert_nodes([mock.node()])          # the commit's index
    close(plan, rejected=["n1"])
    assert overlay.ports_on(["n1", "n2"], old.index) == {"n2": {20001}}
    assert "n1" not in overlay._ports
    # every node rejected: nothing is kept at all
    plan = registered(overlay, store, {"n3": [20000]})
    close(plan, rejected=["n3"])
    assert "n3" not in overlay._ports and len(overlay._closed) == 1


def test_a_confirmed_entrys_ports_go_once_no_older_snapshot_is_left():
    overlay, store = InflightOverlay(), StateStore()
    old = store.snapshot()
    plan = registered(overlay, store, {"n1": [20000, 20001]})
    store.upsert_nodes([mock.node()])
    close(plan)
    assert overlay.stats["confirmed"] == 1 and not overlay._entries
    # usage closed with the commit; the ports stay for the old snapshot
    assert overlay.open_entries() == []
    assert overlay.ports_on(["n1"], old.index) == {"n1": {20000, 20001}}
    # ... and are nothing new to a snapshot that holds the commit
    assert overlay.ports_on(["n1"], store.latest_index) == {}
    overlay.retire()
    assert len(overlay._closed) == 1           # `old` is still alive
    old.close()
    overlay.retire()
    assert not overlay._closed and not overlay._ports
    assert overlay.stats["ports_retired"] == 1


def test_the_ttl_backstop(monkeypatch):
    overlay, store = InflightOverlay(), StateStore()
    pinned = store.snapshot()                  # never released
    plan = registered(overlay, store, {"n1": [20000]})
    store.upsert_nodes([mock.node()])
    close(plan)
    registered(overlay, store, {"n2": [20000]})  # never confirmed
    monkeypatch.setattr(overlay_mod, "ENTRY_TTL", -1.0)
    overlay.retire()
    assert not overlay._closed and "n1" not in overlay._ports
    assert overlay.open_entries() == []
    assert not overlay._ports and overlay.stats["expired"] == 1
    assert pinned.index < store.latest_index


def test_an_entry_without_a_store_is_kept_until_the_ttl(monkeypatch):
    overlay = InflightOverlay()
    plan = registered(overlay, None, {"n1": [20000]})
    close(plan)
    assert overlay.ports_on(["n1"], 10 ** 9) == {"n1": {20000}}
    monkeypatch.setattr(overlay_mod, "ENTRY_TTL", -1.0)
    overlay.retire()
    assert not overlay._ports


# -- one answer to "which ports are taken" -----------------------------------

def test_the_port_index_is_reserved_snapshot_plan_and_overlay():
    store = StateStore()
    node = grid_node(0)
    node.reserved.reserved_ports = [20001]
    job = grid_job(24)
    store.upsert_nodes([node])
    store.upsert_job(job)
    committed = mock.alloc(job, node)
    committed.allocated_ports = [AllocatedPort(label="p0", value=20000)]
    store.upsert_allocs([committed])
    plan = Plan()
    planned = mock.alloc(job, node)
    planned.allocated_ports = [AllocatedPort(label="p0", value=20002)]
    plan.node_allocation[node.id] = [planned]
    registered(INFLIGHT, store, {node.id: [20003, 20002]})
    ctx = EvalContext(store.snapshot(), plan, eval_id="e")
    idx = ctx.port_index(node)
    assert {20000, 20001, 20002, 20003} <= idx.used
    assert idx.inflight and not idx.collision
    got, err = idx.assign_ports(job.task_groups[0].combined_resources())
    assert not err and [p.value for p in got] == [20004, 20005]
    # a caller that took its victims out hands its own list in
    assert 20000 not in ctx.port_index(node, []).used
    # the same taken set gives the same ports: lowest free first
    again, _ = ctx.port_index(node).assign_ports(
        job.task_groups[0].combined_resources())
    assert [p.value for p in again] == [20004, 20005]


def test_the_lowest_free_port_skips_what_is_taken_once():
    node = grid_node(0)
    idx = NetworkIndex(node)
    idx.add_taken(range(20000, 20500))
    ask = grid_job(24).task_groups[0].combined_resources()
    out = [p.value for _ in range(3) for p in idx.assign_ports(ask)[0]]
    assert out == list(range(20500, 20506))
    idx.add_taken([20501])                     # known already: no news
    assert idx.inflight and not idx.colliding_ports


def test_the_host_scorer_sees_in_flight_ports():
    store = StateStore()
    node = grid_node(0)
    job = grid_job(24)
    store.upsert_nodes([node])
    store.upsert_job(job)
    registered(INFLIGHT, store, {node.id: [20000, 20001]})
    ctx = EvalContext(store.snapshot(), Plan(), eval_id="e")
    tg = job.task_groups[0]
    option = TPUPlacer()._host_one(
        ctx, job, tg, [node], PlacementRequest(name="x", task_group=tg),
        False, False, 0)
    assert [p.value for p in option.allocated_ports] == [20002, 20003]


def test_a_host_scored_remainder_registers_its_ports():
    """At or under HOST_CUTOVER the host scorer places the group; what
    it chose is in the overlay for the evaluations behind it."""
    job = grid_job(TPUPlacer.HOST_CUTOVER)
    store = store_with(16, [job])
    planner = GatedPlanner(store)
    seen = {}
    hold = planner.applier.apply

    def apply(plan):
        seen.update(INFLIGHT.ports_on(list(plan.node_allocation), 0))
        return hold(plan)

    planner.applier.apply = apply
    NewScheduler("service", store.snapshot(), planner,
                 sched_config=tpu_config()).process(mock.eval_for(job))
    allocs = live(store.snapshot(), job)
    assert len(allocs) == TPUPlacer.HOST_CUTOVER
    for a in allocs:
        assert {p.value for p in a.allocated_ports} <= seen[a.node_id]


def test_a_group_without_ports_does_no_port_work():
    TRACER.set_enabled(True)
    TRACER.clear()
    before = {name: REGISTRY.get(name) for name in PORT_COUNTERS}
    job = grid_job(24, ports=0)
    store = store_with(16, [job])
    planner = GatedPlanner(store)
    registered_ports = []
    register = InflightOverlay.register

    def spy(self, cluster, rows, deltas, plan, ports=None):
        registered_ports.append(ports)
        register(self, cluster, rows, deltas, plan, ports)

    InflightOverlay.register = spy
    try:
        NewScheduler("service", store.snapshot(), planner,
                     sched_config=tpu_config()).process(mock.eval_for(job))
    finally:
        InflightOverlay.register = register
    assert len(live(store.snapshot(), job)) == 24
    assert registered_ports == [None]
    assert not INFLIGHT._ports and not INFLIGHT._closed
    names = {r[R_NAME] for r in TRACER.spans()}
    assert "placer.register" in names and "placer.rows" in names
    assert "placer.ports" not in names
    assert {n: REGISTRY.get(n) for n in PORT_COUNTERS} == before


# -- tracing -----------------------------------------------------------------

def test_the_spans_and_counters_of_a_port_asking_group():
    TRACER.set_enabled(True)
    TRACER.clear()
    before = {name: REGISTRY.get(name) for name in PORT_COUNTERS}
    jobs = [grid_job(24) for _ in range(2)]
    store = store_with(32, jobs)
    race(store, jobs)
    spans = TRACER.spans()
    locked = [r for r in spans if r[R_NAME] == "placer.locked"]
    assert len(locked) == 2
    touched = 0
    for hold in locked:
        kids = sorted((r for r in spans if r[R_PARENT] == hold[R_ID]),
                      key=lambda r: r[R_T0])
        assert [r[R_NAME] for r in kids][-3:] == [
            "placer.fetch", "placer.ports", "placer.register"]
        args = kids[-2][R_ARGS]
        assert args["ports"] == 48 and 12 <= args["nodes"] <= 24
        touched += args["nodes"]
    rows = [r for r in spans if r[R_NAME] == "placer.rows"]
    assert [r[R_ARGS]["k"] for r in rows] == [24, 24]
    moved = {n: REGISTRY.get(n) - before[n] for n in PORT_COUNTERS}
    assert moved["nomad.placer.ports_assigned"] == 96
    assert moved["nomad.placer.port_nodes"] == touched
    # the second racer met the first one's ports on a half-filled node
    assert 0 < moved["nomad.placer.port_nodes_inflight"] <= touched / 2


def test_the_applier_counts_a_port_collision():
    store = StateStore()
    node = grid_node(0)
    job = grid_job(24)
    store.upsert_nodes([node])
    store.upsert_job(job)
    applier = PlanApplier(store, PlanQueue())
    before = REGISTRY.get("nomad.plan.port_collisions")

    def plan_with(port):
        plan = Plan(job=job)
        alloc = mock.alloc(job, node)
        alloc.allocated_ports = [AllocatedPort(label="p0", value=port)]
        plan.node_allocation[node.id] = [alloc]
        return plan

    assert applier.apply(plan_with(20000)).rejected_nodes == []
    assert applier.apply(plan_with(20000)).rejected_nodes == [node.id]
    assert applier.apply(plan_with(20001)).rejected_nodes == []
    assert applier.stats["port_collisions"] == 1
    assert applier.stats["nodes_rejected"] == 1
    assert REGISTRY.get("nomad.plan.port_collisions") - before == 1


# -- the served path ---------------------------------------------------------

def packing(snap, job_ids) -> float:
    """Mean BestFit fitness of the jobs' placements, by the
    benchmark's plain formula (benchmark/reference/fitness.py)."""
    nodes = sorted(snap.nodes(), key=lambda n: n.id)
    cap = np.array([n.available_vec()[:2] for n in nodes], np.float64)
    used = np.array([snap.node_usage(n.id)[:2]
                     if snap.node_usage(n.id) is not None else (0.0, 0.0)
                     for n in nodes], np.float64)
    counts = np.array([sum(a.job_id in job_ids and not a.terminal_status()
                           for a in snap.allocs_by_node(n.id))
                       for n in nodes], np.int64)
    return mean_fitness(cap, used, counts)


def test_served_path_six_racing_jobs_whole_sound_and_packed():
    """A Server with 4 workers, 128 nodes (the issue's 64 hold 128 of
    the grid's tasks, not 240), 6 jobs x 40 with 2 ports a task,
    submitted into a paused broker and resumed. Every job whole,
    no evaluation `failed`, no row rejected, the plain reference finds
    nothing; and the packing is what the host scheduler reaches with
    the same jobs one at a time, to within 2% (240 of 256 slots are
    taken either way, so both end on nearly the same fill)."""
    fleet = [grid_node(i) for i in range(128)]
    jobs = [grid_job(40) for _ in range(6)]
    server = Server(ServerConfig(num_workers=4, sched_config=tpu_config()))
    server.start()
    try:
        for node in fleet:
            server.register_node(node)
        server.broker.set_enabled(False)
        for job in jobs:
            server.register_job(job)
        server.broker.set_enabled(True)
        server._restore_evals()
        assert server.wait_for_idle(120.0)
        snap = server.store.snapshot()
        assert [len(live(snap, j)) for j in jobs] == [40] * 6
        statuses = [ev.status for j in jobs for ev in snap.evals_by_job(j.id)]
        assert statuses == ["complete"] * 6
        stats = server.plan_applier.stats
        assert stats["nodes_rejected"] == 0 and stats["port_collisions"] == 0
        nodes, allocs = plain_state(snap)
        assert reference.violations(nodes, allocs) == []
        assert reference.census(nodes, allocs)["ports"] == 480
        served = packing(snap, {j.id for j in jobs})
    finally:
        server.stop()
    host = Harness()                # the same fleet and jobs, afresh
    host.store.upsert_nodes([grid_node(i) for i in range(128)])
    jobs = [grid_job(40) for _ in range(6)]
    for job in jobs:
        host.store.upsert_job(job)
        host.process(mock.eval_for(job))
    snap = host.store.snapshot()
    assert [len(live(snap, j)) for j in jobs] == [40] * 6
    reference_packing = packing(snap, {j.id for j in jobs})
    assert served >= reference_packing * (1 - 0.02), (served,
                                                      reference_packing)
