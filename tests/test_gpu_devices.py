"""GPU service jobs on a fleet of which some nodes carry GPUs: typed
device attributes, the device verdict by instance, instances chosen
under the solve lock and read by racing evaluations.

- `${device.attr.memory} >= 2 GiB` compares 11 GiB with 2 GiB after
  unit conversion (reference plugins/shared/structs/attribute.go);
  values of two unit families or two types do not compare, and the
  constraint fails;
- `device_mask` applies each ask's constraints (reference DeviceChecker);
- `allocs_fit(check_devices=True)` refuses an instance held twice, the
  applier also a new placement's instance that is not the node's, and
  counts both; a running allocation on an instance the node dropped
  leaves the node open, and a GPU job still preempts cpu work;
- two evaluations at one snapshot choose disjoint instances on the
  half-filled nodes BestFit sends both to, and a `count = 2` ask reads
  the other's instances in its count column (both fail on the tree
  before this: every racer takes the lowest free instance of its own
  snapshot, and the applier counted instances, not ids);
- the served path against the plain reference
  `benchmark/reference/devices.py`;
- a group that asks no device opens no device span and counts nothing.
"""

import numpy as np
import pytest

from benchmark.deploy.single_agent_gpu import (carries_gpus, plain_allocs,
                                               plain_nodes)
from benchmark.reference import devices as reference
from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue
from nomad_tpu.obs import TRACER
from nomad_tpu.obs.trace import R_NAME
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.devices import matching_groups
from nomad_tpu.scheduler.feasible import device_mask
from nomad_tpu.scheduler.reconcile import PlacementRequest
from nomad_tpu.state import StateStore
from nomad_tpu.scheduler.rank import NodeScorer
from nomad_tpu.structs import Affinity, Constraint, Spread, allocs_fit, enums
from nomad_tpu.structs.funcs import foreign_devices
from nomad_tpu.structs.attribute import Attribute, parse_attribute
from nomad_tpu.structs.plan import Plan
from nomad_tpu.structs.resources import RequestedDevice, Resources
from nomad_tpu.tensor.overlay import INFLIGHT, InflightOverlay
from nomad_tpu.tensor.placer import TPUPlacer
from test_inflight_ports import grid_node, live, race

DEVICE_COUNTERS = ("nomad.placer.device_nodes",
                   "nomad.placer.device_nodes_inflight",
                   "nomad.placer.devices_assigned")
MEMORY = Constraint(ltarget="${device.attr.memory}", operand=">=",
                    rtarget="2 GiB")
CLOCK = Affinity(ltarget="${device.attr.graphics_clock}", operand=">",
                 rtarget="1.4 GHz", weight=50)


@pytest.fixture(autouse=True)
def clean_overlay():
    def wipe():
        with INFLIGHT._lock:
            INFLIGHT._entries.clear()
            INFLIGHT._closed.clear()
            for table in INFLIGHT._held.values():
                table.clear()
    wipe()
    yield
    wipe()


def gpu_ask(count: int = 1) -> RequestedDevice:
    """The documented device block: one nvidia/gpu with 2 GiB or more."""
    return RequestedDevice(name="nvidia/gpu", count=count,
                           constraints=[MEMORY], affinities=[CLOCK])


def gpu_grid_node(i: int, racks: int = 4):
    """upstream's grid node; NvidiaNode()'s two GPUs on half of them."""
    node = grid_node(i, racks)
    if carries_gpus(i, {"every": 4, "first": 2}):
        mock.add_nvidia_gpus(node)
    return node


def gpu_job(count: int, gpus: int = 1, spread: bool = True):
    """The grid's task with one device ask, over HOST_CUTOVER so the
    scan places it."""
    job = mock.job()
    job.constraints = []
    tg = job.task_groups[0]
    tg.count = count
    res = tg.tasks[0].resources
    res.cpu, res.memory_mb = 6000, 6000
    res.networks = []
    res.devices = [gpu_ask(gpus)]
    if spread:
        tg.spreads = [Spread(attribute="${meta.rack}", weight=50)]
    return job


def gpu_store(nodes: int, jobs: list):
    store = StateStore()
    store.upsert_nodes([gpu_grid_node(i) for i in range(nodes)])
    for job in jobs:
        store.upsert_job(job)
    return store


def held(snap, node_id):
    return [(gid, i) for a in snap.allocs_by_node(node_id)
            if not a.terminal_status()
            for gid, ids in (a.allocated_devices or {}).items()
            for i in ids]


# -- typed attributes ---------------------------------------------------------

@pytest.mark.parametrize("left, operand, right, expected", [
    ("11 GiB", ">=", "2 GiB", True),
    ("1480 MHz", ">", "1.4 GHz", True),
    ("1480 MHz", "<", "1.4 GHz", False),
    ("11 GB/s", ">=", "2 GiB", False),         # two families: no compare
    ("11 GB/s", "<", "2 GiB", False),
    ("11 GiB", "!=", "11 GB/s", False),
    ("11 GiB", "=", "11264 MiB", True),
    ("1 GB", "<", "1 GiB", True),
    ("11 GB/s", ">", "10 GiB/s", True),
    ("500 mW", "<", "1 W", True),
    ("11 GiB", "=", "11", False),              # a unit on one side only
    (3584, ">", "3000", True),                 # int against int
    (3584, "=", "3584.0", True),               # int against float
    ("2.5", "<", "3", True),
    ("abc", "<", "abd", True),
    ("true", "=", "true", True),
    ("true", "=", "1", False),                 # bool against int
    ("1080ti", "regexp", "^10", True),
    (3584, "regexp", "^35", False),            # a string operator on an int
])
def test_device_attributes_compare_typed(left, operand, right, expected):
    node = mock.nvidia_node()
    node.resources.devices[0].attributes["probe"] = left
    ask = RequestedDevice(name="gpu", constraints=[Constraint(
        ltarget="${device.attr.probe}", operand=operand, rtarget=right)])
    assert bool(matching_groups(node, ask)) is expected


def test_parse_attribute_reads_numbers_units_bools_and_strings():
    assert parse_attribute("11 GiB") == Attribute(11, "GiB")
    assert parse_attribute("1.4 GHz") == Attribute(1.4, "GHz")
    assert parse_attribute("11 GB/s") == Attribute(11, "GB/s")
    assert parse_attribute("3584") == Attribute(3584)
    assert parse_attribute("true") == Attribute(True)
    assert parse_attribute("1_0") == Attribute("1_0")   # Go's forms only
    assert parse_attribute("hello GB") == Attribute("hello GB")
    assert parse_attribute("") == Attribute("")


def test_the_nvidia_node_meets_the_documented_ask():
    node = mock.nvidia_node()
    (group,) = node.resources.devices
    assert (group.vendor, group.type, group.name) == ("nvidia", "gpu",
                                                      "1080ti")
    assert len(group.instance_ids) == 2
    assert matching_groups(node, gpu_ask()) == [group]
    small = RequestedDevice(name="nvidia/gpu", constraints=[Constraint(
        ltarget="${device.attr.memory}", operand=">=", rtarget="12 GiB")])
    assert matching_groups(node, small) == []


def test_device_mask_applies_each_asks_constraints():
    rich, plain, poor = mock.nvidia_node(), mock.node(), mock.nvidia_node()
    poor.resources.devices[0].attributes["memory"] = "1 GiB"
    tg = gpu_job(24).task_groups[0]
    assert device_mask(tg, [rich, plain, poor]).tolist() == [
        True, False, False]
    tg.tasks[0].resources.devices = [RequestedDevice(name="nvidia/gpu")]
    assert device_mask(tg, [rich, plain, poor]).tolist() == [
        True, False, True]
    tg.tasks[0].resources.devices = [gpu_ask(3)]
    assert device_mask(tg, [rich, plain, poor]).tolist() == [
        False, False, False]


def test_a_device_ask_survives_the_wire():
    from nomad_tpu.api.codec import from_dict, to_dict
    from nomad_tpu.structs import Job

    job = from_dict(Job, to_dict(gpu_job(24)))
    (ask,) = job.task_groups[0].tasks[0].resources.devices
    assert ask.constraints == [MEMORY] and ask.affinities == [CLOCK]


# -- the verdict by instance -----------------------------------------------

def test_allocs_fit_refuses_an_instance_held_twice():
    node = mock.nvidia_node()
    (group,) = node.resources.devices
    a, b = mock.alloc(), mock.alloc()
    for x in (a, b):
        x.allocated_vec = np.zeros_like(x.allocated_vec)
    a.allocated_devices = {group.id: [group.instance_ids[0]]}
    b.allocated_devices = {group.id: [group.instance_ids[1]]}
    assert allocs_fit(node, [a, b], check_devices=True)[0]
    # two allocations, two instances: a count check passes this
    b.allocated_devices = {group.id: [group.instance_ids[0]]}
    fit, dim, _ = allocs_fit(node, [a, b], check_devices=True)
    assert not fit and dim == "device oversubscribed"
    # an instance the node does not have is skipped, as upstream's
    # DeviceAccounter.AddAllocs skips it: the applier judges new ones
    for foreign in ({group.id: ["not-an-instance"]},
                    {"nvidia/gpu/t4": [group.instance_ids[1]]}):
        b.allocated_devices = foreign
        assert allocs_fit(node, [a, b], check_devices=True)[0]
        assert foreign_devices(node, [b]) and not foreign_devices(node, [a])


def test_the_applier_refuses_a_new_instance_that_is_not_the_nodes():
    store = StateStore()
    node = gpu_grid_node(0)
    job = gpu_job(24)
    store.upsert_nodes([node])
    store.upsert_job(job)
    plan = Plan(job=job)
    new = mock.alloc(job, node)
    new.allocated_devices = {"nvidia/gpu/1080ti": ["not-an-instance"]}
    plan.node_allocation[node.id] = [new]
    applier = PlanApplier(store, PlanQueue())
    assert applier.apply(plan).rejected_nodes == [node.id]
    assert applier.stats["device_collisions"] == 1


def test_a_running_alloc_on_a_dropped_instance_leaves_the_node_open():
    """The node no longer has the instance a running alloc holds (it was
    unfingerprinted): a new placement on the node's own instance lands,
    by the applier and by the host scorer."""
    store = StateStore()
    node = gpu_grid_node(0)
    (group,) = node.resources.devices
    job = gpu_job(24)
    store.upsert_nodes([node])
    store.upsert_job(job)
    old = mock.alloc(job, node)
    old.allocated_vec = np.zeros_like(old.allocated_vec)
    old.allocated_devices = {group.id: ["dropped"]}
    store.upsert_allocs([old])
    tg = job.task_groups[0]
    option = NodeScorer(EvalContext(store.snapshot(), Plan(), eval_id="e"),
                        job, tg).rank(node)
    assert option is not None
    assert option.allocated_devices == {group.id: [group.instance_ids[0]]}
    plan = Plan(job=job)
    new = mock.alloc(job, node)
    new.allocated_devices = option.allocated_devices
    plan.node_allocation[node.id] = [new]
    applier = PlanApplier(store, PlanQueue())
    assert applier.apply(plan).rejected_nodes == []
    assert applier.stats["device_collisions"] == 0


def test_a_gpu_job_preempts_lower_priority_cpu_work():
    """A node full of low-priority work that asks no device: the GPU job
    at priority 100 evicts one of it for cpu and memory, and is handed
    the node's first instance."""
    store = StateStore()
    node = gpu_grid_node(0)
    (group,) = node.resources.devices
    low = mock.job(priority=10)
    job = gpu_job(24)
    job.priority = 100
    store.upsert_nodes([node])
    store.upsert_job(low)
    store.upsert_job(job)
    filler = [mock.alloc(low, node, index=i) for i in range(2)]
    for a in filler:
        a.allocated_vec = Resources(cpu=6000, memory_mb=6000).vec()
    store.upsert_allocs(filler)
    ctx = EvalContext(store.snapshot(), Plan(), eval_id="e")
    tg = job.task_groups[0]
    assert NodeScorer(ctx, job, tg).rank(node) is None
    option = NodeScorer(ctx, job, tg, preemption_enabled=True).rank(node)
    assert option is not None
    assert [v.id for v in option.preempted_allocs] in (
        [filler[0].id], [filler[1].id])
    assert option.allocated_devices == {group.id: [group.instance_ids[0]]}


def test_the_applier_counts_a_device_collision():
    store = StateStore()
    node = gpu_grid_node(0)
    job = gpu_job(24)
    store.upsert_nodes([node])
    store.upsert_job(job)
    (group,) = node.resources.devices
    first = mock.alloc(job, node)
    first.allocated_devices = {group.id: [group.instance_ids[0]]}
    store.upsert_allocs([first])
    plan = Plan(job=job)
    second = mock.alloc(job, node)
    second.allocated_devices = {group.id: [group.instance_ids[0]]}
    plan.node_allocation[node.id] = [second]
    before = REGISTRY.get("nomad.plan.device_collisions")
    applier = PlanApplier(store, PlanQueue())
    result = applier.apply(plan)
    assert result.rejected_nodes == [node.id]
    assert applier.stats["device_collisions"] == 1
    assert REGISTRY.get("nomad.plan.device_collisions") == before + 1


# -- one answer to "which instances are taken" ---------------------------------

def test_the_device_index_is_snapshot_plan_and_overlay():
    store = StateStore()
    node = gpu_grid_node(0)
    (group,) = node.resources.devices
    store.upsert_nodes([node])
    ctx = EvalContext(store.snapshot(), Plan(), eval_id="e")
    assert not ctx.device_index(node).inflight
    plan = Plan()
    INFLIGHT.register(_Cluster(store, [node.id]), np.arange(1),
                      np.ones((1, 4)), plan, None,
                      {node.id: {group.id: [group.instance_ids[0]]}})
    idx = ctx.device_index(node)
    assert idx.inflight and idx.used[group.id] == {group.instance_ids[0]}
    assert idx.assign([gpu_ask()]) == {group.id: [group.instance_ids[1]]}
    assert ctx.device_index(node).assign([gpu_ask(2)]) is None


def test_the_host_scorer_sees_in_flight_instances():
    store = StateStore()
    node = gpu_grid_node(0)
    (group,) = node.resources.devices
    job = gpu_job(24)
    store.upsert_nodes([node])
    store.upsert_job(job)
    INFLIGHT.register(_Cluster(store, [node.id]), (), None, Plan(), None,
                      {node.id: {group.id: [group.instance_ids[0]]}})
    ctx = EvalContext(store.snapshot(), Plan(), eval_id="e")
    tg = job.task_groups[0]
    option = TPUPlacer()._host_one(
        ctx, job, tg, [node], PlacementRequest(name="x", task_group=tg),
        False, False, 0)
    assert option.allocated_devices == {group.id: [group.instance_ids[1]]}


def test_closed_instances_stay_for_older_snapshots_only():
    overlay, store = InflightOverlay(), StateStore()
    old = store.snapshot()
    plan = Plan()
    overlay.register(_Cluster(store, ["n1", "n2"]), np.arange(2),
                     np.ones((2, 4)), plan, None,
                     {"n1": {"g": ["i0"]}, "n2": {"g": ["i1"]}})
    store.upsert_nodes([mock.node()])              # the commit's index
    for hook in plan.post_apply_hooks:
        hook(type("R", (), {"rejected_nodes": ["n2"]})())
    nodes = ["n1", "n2"]
    assert overlay.devices_on(nodes, old.index) == {"n1": {"g": {"i0"}}}
    assert overlay.devices_held(old.index) == [{"n1": {"g": ["i0"]}}]
    assert overlay.devices_on(nodes, store.latest_index) == {}
    assert overlay.devices_held(store.latest_index) == []
    old.close()
    overlay.retire()
    assert not overlay._devices and overlay.stats["retired"] == 1


class _Cluster:
    def __init__(self, store, node_ids):
        from types import SimpleNamespace

        self._store = store
        self.nodes = [SimpleNamespace(id=n) for n in node_ids]
        self.node_index = {n: i for i, n in enumerate(node_ids)}


# -- the race ----------------------------------------------------------------

@pytest.mark.parametrize("racers", [2, 4])
def test_racing_evaluations_choose_disjoint_instances(racers):
    jobs = [gpu_job(24) for _ in range(racers)]
    store = gpu_store(64 * racers, jobs)
    before = REGISTRY.get("nomad.placer.device_nodes_inflight")
    planner = race(store, jobs)
    stats = planner.applier.stats
    # no row lost at the applier, every job whole at its first plan
    assert stats["nodes_rejected"] == 0 and stats["device_collisions"] == 0
    assert len(planner.plans) == racers
    snap = store.snapshot()
    assert [len(live(snap, j)) for j in jobs] == [24] * racers
    nodes, allocs = plain_nodes(snap), plain_allocs(snap)
    assert reference.violations(nodes, allocs) == []
    assert reference.census(nodes, allocs)["instances"] == 24 * racers
    # ... and they did meet on half-filled nodes: a node holds two
    # jobs' allocations, on its two instances
    shared = [n for n in nodes
              if len({a.job_id for a in snap.allocs_by_node(n)}) > 1]
    assert shared
    for node_id in shared:
        ids = held(snap, node_id)
        assert len(ids) == len(set(ids)) == 2
    assert REGISTRY.get("nomad.placer.device_nodes_inflight") > before


def test_a_two_gpu_ask_reads_in_flight_instances_in_its_count_column(
        monkeypatch):
    """Each task takes both GPUs of a node, so the device binds at one
    a node while cpu would take two. The second racer's count column
    holds the first's instances (folded under the lock): it never
    chooses a node the first took, and no request falls back to the
    host selector."""
    calls = []
    host_one = TPUPlacer._host_one

    def spy(self, *args, **kw):
        calls.append(args)
        return host_one(self, *args, **kw)

    monkeypatch.setattr(TPUPlacer, "_host_one", spy)
    jobs = [gpu_job(24, gpus=2, spread=False) for _ in range(2)]
    store = gpu_store(128, jobs)
    before = REGISTRY.get("nomad.placer.device_nodes_inflight")
    planner = race(store, jobs)
    stats = planner.applier.stats
    assert stats["nodes_rejected"] == 0 and stats["device_collisions"] == 0
    assert len(planner.plans) == 2 and not calls
    assert REGISTRY.get("nomad.placer.device_nodes_inflight") == before
    snap = store.snapshot()
    assert [len(live(snap, j)) for j in jobs] == [24, 24]
    for node in snap.nodes():
        assert len(snap.allocs_by_node(node.id)) <= 1
    assert reference.violations(plain_nodes(snap), plain_allocs(snap)) == []


def test_blind_to_the_overlays_instances_the_racers_collide(monkeypatch):
    """The control: the tree before this change's view planted (nobody
    reads the overlay's instances). The applier's verdict by instance
    catches every collision and counts it."""
    monkeypatch.setattr(InflightOverlay, "devices_on",
                        lambda self, *a, **kw: {})
    monkeypatch.setattr(InflightOverlay, "devices_held",
                        lambda self, *a, **kw: [])
    jobs = [gpu_job(24) for _ in range(4)]
    store = gpu_store(256, jobs)
    planner = race(store, jobs)
    stats = planner.applier.stats
    assert stats["device_collisions"] > 0
    assert stats["nodes_rejected"] >= stats["device_collisions"]
    assert len(planner.plans) > 4
    nodes, allocs = plain_nodes(store.snapshot()), plain_allocs(
        store.snapshot())
    assert reference.violations(nodes, allocs) == []


# -- the served path against the plain reference -------------------------------

def test_a_served_gpu_drain_holds_every_guarantee_line():
    """512 nodes, half with NvidiaNode()'s two GPUs (512 slots), four
    racing jobs of 100 with the documented device block and the rack
    spread, through a Server's broker, workers and applier: every job
    whole, no evaluation failed, no row rejected, and the plain
    reference finds every device, capacity and spread line held."""
    from nomad_tpu.core.server import Server, ServerConfig
    from nomad_tpu.structs.operator import SchedulerConfiguration

    fleet = [gpu_grid_node(i, racks=8) for i in range(512)]
    jobs = [gpu_job(100) for _ in range(4)]
    server = Server(ServerConfig(num_workers=4, sched_config=(
        SchedulerConfiguration(
            scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK))))
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
        assert [len(live(snap, j)) for j in jobs] == [100] * 4
        assert not [ev for ev in snap.evals()
                    if ev.status in ("failed", "blocked")]
        stats = server.plan_applier.stats
        assert stats["device_collisions"] == 0
        nodes, allocs = plain_nodes(snap), plain_allocs(snap)
        assert reference.census(nodes, allocs) == {
            "allocations": 400, "device_allocations": 400,
            "instances": 400, "nodes": 512, "gpu_nodes": 256}
        assert reference.violations(nodes, allocs) == []
        assert reference.spread_violations(
            nodes, allocs, {j.id for j in jobs}, 2) == []
        # the nodes without a GPU took none of it
        assert all(nodes[a["node"]]["groups"] for a in allocs)
    finally:
        server.stop()


def test_the_reference_finds_each_kind_of_violation():
    node = mock.nvidia_node()
    (group,) = node.resources.devices
    nodes = {"n": {"cpu": 14000.0, "mem": 32000.0, "rack": "r0",
                   "groups": [{"id": group.id, "vendor": "nvidia",
                               "type": "gpu", "name": "1080ti",
                               "instances": list(group.instance_ids),
                               "attributes": dict(group.attributes)}]},
             "bare": {"cpu": 14000.0, "mem": 32000.0, "rack": "r1",
                      "groups": []}}
    ask = {"name": "nvidia/gpu", "count": 1,
           "constraints": [["${device.attr.memory}", ">=", "2 GiB"]]}
    i0, i1 = group.instance_ids

    def alloc(name, node="n", devices=None, cpu=6000.0):
        return {"id": name, "node": node, "job": "j", "cpu": cpu,
                "mem": 6000.0, "asks": [ask],
                "devices": devices if devices is not None
                else {group.id: [i0]}}

    assert reference.violations(nodes, [
        alloc("a"), alloc("b", devices={group.id: [i1]})]) == []
    cases = [
        [alloc("a"), alloc("b")],                           # twice
        [alloc("a", devices={group.id: ["x"]})],            # not the node's
        [alloc("a", devices={group.id: [i0, i1]})],         # surplus
        [alloc("a", devices={})],                           # missing
        [alloc("a", node="bare", devices={})],              # no such group
        [alloc("a", cpu=9000.0), alloc("b", cpu=9000.0,
                                       devices={group.id: [i1]})],
    ]
    for allocs in cases:
        assert reference.violations(nodes, allocs), allocs
    small = dict(ask, constraints=[["${device.attr.memory}", ">=",
                                    "12 GiB"]])
    assert reference.violations(nodes, [dict(alloc("a"), asks=[small])])
    assert reference.compare("11 GB/s", "2 GiB") is None
    assert reference.compare("1480 MHz", "1.4 GHz") == 1


# -- a group that asks no device ------------------------------------------------

def test_a_group_without_a_device_ask_does_no_device_work():
    TRACER.set_enabled(True)
    TRACER.clear()
    before = {name: REGISTRY.get(name) for name in DEVICE_COUNTERS}
    job = gpu_job(24)
    job.task_groups[0].tasks[0].resources.devices = []
    store = gpu_store(32, [job])
    race(store, [job])
    assert len(live(store.snapshot(), job)) == 24
    names = {r[R_NAME] for r in TRACER.spans()}
    assert "placer.register" in names and "placer.rows" in names
    assert "placer.devices" not in names
    assert "build.device_columns" not in names
    assert {n: REGISTRY.get(n) for n in DEVICE_COUNTERS} == before
    assert not INFLIGHT._devices


def test_a_device_group_opens_both_spans():
    """A group of fresh placements that asks a device leaves the scan
    as one block (tests/test_device_blocks.py): placer.block, and no
    placer.rows."""
    TRACER.set_enabled(True)
    TRACER.clear()
    job = gpu_job(300)
    store = gpu_store(320, [job])
    race(store, [job])
    assert len(live(store.snapshot(), job)) == 300
    names = [r[R_NAME] for r in TRACER.spans()]
    assert names.count("placer.devices") == 1
    assert names.count("build.device_columns") >= 1
    assert names.count("placer.block") == 1
    assert "placer.rows" not in names


# -- the configuration, the cell and the deploy kind -------------------------

CELL = "grid.gpu.spread.300"
OWN = ["placer.devices_ms", "build.device_columns_ms",
       "placer.device_nodes_inflight_pct", "applier.device_collisions"]


def test_the_cell_is_the_grid_with_a_gpu_half_and_the_device_block():
    import json
    from pathlib import Path

    from benchmark.harness import load_cell

    root = Path(__file__).resolve().parents[1]
    grid = json.loads((root / "benchmark/configs/grid-10k.json").read_text())
    bench, cell, config, traffic = load_cell(CELL, toy=False)
    assert cell == {"name": CELL, "config": "grid-10k-gpu",
                    "traffic": "spread.gpu.300", "chips": 1,
                    "why": cell["why"]} and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == config["reduced"] == ["servers"]
    assert config["deploy"] == "single_agent_gpu"
    for key in ("node_mix", "agent", "nodes", "job_sizes", "chips",
                "servers", "reduced_why"):
        assert config[key] == grid[key], key
    for key in ("grid", "nodes", "job"):
        assert config["upstream"][key] == grid["upstream"][key], key
    (cls,) = traffic["classes"]
    assert cls["devices"] == config["task_ask"]["devices"]
    assert (cls["cpu"], cls["mem"]) == ([6000] * 2, [6000] * 2)
    assert not cls["ports"] and cls["spread"]["attribute"] == "${meta.rack}"
    gpus = sum(carries_gpus(i, config["gpu_nodes"])
               for i in range(config["nodes"]))
    assert gpus == config["nodes"] // 2
    fits = gpus * config["gpu_nodes"]["instances"]
    assert config["allocations_that_fit"] == fits
    assert traffic["jobs"] * cls["count"]["cycle"][0] == 0.75 * fits
    # every rack holds GPU nodes: 25 racks are coprime to the rule's 4
    racks = config["node_mix"]["racks"]
    assert len({i % racks for i in range(config["nodes"])
                if carries_gpus(i, config["gpu_nodes"])}) == racks
    # toy: half the toy fleet with GPUs, the toy's slots are its fit
    _, _, toy_config, toy = load_cell(CELL, toy=True)
    toy_gpus = sum(carries_gpus(i, config["gpu_nodes"])
                   for i in range(toy_config["toy"]["nodes"]))
    assert toy_config["toy"]["allocations_that_fit"] == 2 * toy_gpus
    assert toy["classes"][0]["devices"] == cls["devices"]


def test_the_new_readers_list_the_cell_and_find_nothing_in_the_parent():
    from benchmark import layers
    from benchmark.harness import load_cell, metrics_of

    bench = load_cell(CELL, toy=False)[0]
    mine = {m["name"]: m for m in metrics_of(bench, "per_layer", CELL)}
    for name in OWN:
        assert mine[name]["workloads"] == [CELL]
        spec = layers.load(name)
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            mine[name]["unit"], mine[name]["layer"], mine[name]["moves"])
    assert "solve_task_group_fused_roofline" not in mine
    assert "placer.rows_ms" in mine and "placer.block_ms" not in mine
    obs = {"spans": {"durations": {"placer.devices": [0.002, 0.004],
                                   "build.device_columns": [0.01]},
                     "self": {}},
           "counters": {"applier": {"device_collisions": 0},
                        "registry": {"nomad.placer.device_nodes": 200,
                                     "nomad.placer.device_nodes_inflight":
                                     150}}}
    got = {k: v["value"] for k, v in layers.read_all(OWN, obs).items()}
    assert got == {"placer.devices_ms": pytest.approx(3.0),
                   "build.device_columns_ms": pytest.approx(10.0),
                   "placer.device_nodes_inflight_pct": pytest.approx(75.0),
                   "applier.device_collisions": 0.0}
    parent = {"spans": {"durations": {}, "self": {}},
              "counters": {"applier": {"port_collisions": 0},
                           "registry": {}}}
    assert layers.read_all(OWN, parent) == {}


def doctored_deployment(doctor=None):
    """A quiesced store of two GPU nodes and a spread job of four, one
    instance each; `doctor(allocs, groups)` edits the rows first."""
    from types import SimpleNamespace

    from benchmark.deploy import single_agent_gpu

    store = StateStore()
    nodes = [gpu_grid_node(0, racks=2), gpu_grid_node(1, racks=2)]
    store.upsert_nodes(nodes)
    job = gpu_job(4)
    store.upsert_job(job)
    allocs = []
    for i in range(4):
        node = nodes[i % 2]
        (group,) = node.resources.devices
        a = mock.alloc(job, node, index=i)
        a.allocated_devices = {group.id: [group.instance_ids[i // 2]]}
        allocs.append(a)
    if doctor is not None:
        doctor(allocs, [n.resources.devices[0] for n in nodes])
    store.upsert_plan_results(allocs, job=job)
    store.upsert_evals([mock.eval_for(job, status="complete")])
    dep = single_agent_gpu.deploy(
        {"nodes": 2, "allocations_that_fit": 4, "spread_slack": 0,
         "toy": {}}, 1, False)
    dep.server = SimpleNamespace(
        store=store, broker=SimpleNamespace(inflight=lambda: 0),
        plan_queue=SimpleNamespace(depth=lambda: 0))
    dep.pause_broker = lambda paused: None
    return dep


def test_a_sound_round_passes_and_the_gpu_line_says_what_it_read(capsys):
    assert doctored_deployment().quiesce() is True
    out = [l for l in capsys.readouterr().out.splitlines()
           if l.startswith("[gpu]")]
    assert out == ["[gpu] round=0 allocations=4 device_allocations=4 "
                   "instances=4 nodes=2 gpu_nodes=2 spread_jobs=1 "
                   "violations=0"]


def _twice(allocs, groups):
    allocs[2].allocated_devices = dict(allocs[0].allocated_devices)


def _foreign(allocs, groups):
    allocs[1].allocated_devices = dict(allocs[0].allocated_devices)


def _none(allocs, groups):
    allocs[3].allocated_devices = {}


@pytest.mark.parametrize("doctor, words", [
    (_twice, " twice, on "), (_foreign, "which is not a device of"),
    (_none, "holds 0 instance(s) for ask 0")])
def test_a_doctored_instance_ends_the_run(doctor, words):
    from benchmark.deploy.single_agent_ports import NotHeld

    dep = doctored_deployment(doctor)
    with pytest.raises(NotHeld) as err:
        dep.quiesce()
    assert str(err.value).startswith("devices: ")
    assert words in str(err.value)
    assert dep.broken is True
