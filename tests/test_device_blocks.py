"""A group that asks devices (and no cores) leaves the per-placement
scan as one AllocBlock that carries its instances
(`AllocBlock.devices`): a run of instance ids a position, those the hold
chose (`TPUPlacer._assign_devices`). The block must be the row loop's
placements node for node and instance for instance, a node that ran
short sending its surplus to the host selector as the row loop does;
the plan applier must hold its instances to the rule it holds rows' to
(allocs_fit's instance check, foreign_devices) without materialising an
Allocation; the store's device-usage rows, the DeviceIndex and the kept
allocation columns must count them; every copy of the block must keep
them."""

import copy
import json

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.core.plan_apply import _OverlaySnapshot
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.state import StateStore
from nomad_tpu.state.persist import dump_store, restore_store
from nomad_tpu.structs import enums
from nomad_tpu.structs.alloc import AllocBlock, BlockDevices
from nomad_tpu.structs.plan import Plan, PlanResult
from nomad_tpu.structs.wire import wire_decode, wire_encode
from nomad_tpu.tensor.overlay import INFLIGHT
from nomad_tpu.tensor.placer import TPUPlacer

from test_gpu_devices import gpu_ask, gpu_grid_node, gpu_job
from test_plan_verify_columnar import Grid, assert_parity
from test_scan_block import _by_name, _drain

COLUMNAR = "nomad.placer.columnar_scan_groups"
SURPLUS = "nomad.placer.device_block_surplus"
GID = "nvidia/gpu/1080ti"


def _wipe():
    with INFLIGHT._lock:
        INFLIGHT._entries.clear()
        INFLIGHT._closed.clear()
        for table in INFLIGHT._held.values():
            table.clear()


@pytest.fixture(autouse=True)
def clean_overlay():
    """The overlay is the process's, and a closed entry's instances
    outlive it: no test reads another's."""
    _wipe()
    yield
    _wipe()


def _devices(a):
    return {gid: list(ids) for gid, ids in (a.allocated_devices or {}).items()}


def _fleet(n):
    """upstream's grid nodes, NvidiaNode()'s two GPUs on half of them."""
    return [gpu_grid_node(i) for i in range(n)]


def _both_arms(monkeypatch, nodes, job, eval_id):
    """The drain as the block arm makes it, then as the row loop does
    (forced through the arm's test), on the same nodes and seed."""
    block_h = _drain(nodes, job, eval_id)
    _wipe()
    monkeypatch.setattr(TPUPlacer, "_wants_ids",
                        staticmethod(lambda ctx, tg: True))
    row_h = _drain(nodes, job, eval_id)
    return block_h, row_h


def _no_instance_twice(h):
    per_node = {}
    for a in h.store.snapshot().allocs():
        if not a.terminal_status():
            per_node.setdefault(a.node_id, []).extend(
                (gid, i) for gid, ids in a.allocated_devices.items()
                for i in ids)
    assert all(len(v) == len(set(v)) for v in per_node.values())
    return per_node


# -- (a) parity with the row loop, instance for instance -------------------


def test_a_device_block_is_the_row_loops_placements_instance_for_instance(
        monkeypatch):
    nodes, job = _fleet(320), gpu_job(300)
    before = REGISTRY.get(COLUMNAR)
    block_h, row_h = _both_arms(monkeypatch, nodes, job, "eval-devices")
    assert REGISTRY.get(COLUMNAR) == before + 1

    (block_plan,), (row_plan,) = block_h.plans, row_h.plans
    (block,) = block_plan.alloc_blocks
    assert not block_plan.node_allocation and not row_plan.alloc_blocks
    assert block.devices.groups == [GID]
    assert len(block.devices.ids) == block.size == 300

    got, want = _by_name(block_h, job), _by_name(row_h, job)
    assert sorted(got) == sorted(want) and len(want) == 300
    for name, row in want.items():
        a = got[name]
        assert a.node_id == row.node_id
        assert _devices(a) == _devices(row)
        assert a.metrics.scores == row.metrics.scores
    # several placements share a node, each on an instance of its own
    per_node = _no_instance_twice(block_h)
    assert max(map(len, per_node.values())) == 2
    for h in (block_h, row_h):
        assert h.evals[-1].status == enums.EVAL_STATUS_COMPLETE
        assert not h.evals[-1].failed_tg_allocs


def test_a_group_that_asks_cores_beside_devices_keeps_the_row_loop():
    store = StateStore()
    job = gpu_job(300)
    tg = job.task_groups[0]
    ctx = EvalContext(store.snapshot(), Plan(), eval_id="e")
    assert not TPUPlacer._wants_ids(ctx, tg)
    tg.tasks[0].resources.cores = 2
    ctx = EvalContext(store.snapshot(), Plan(), eval_id="e")
    assert TPUPlacer._wants_ids(ctx, tg)


# -- (b) a node that runs short of instances ------------------------------


def test_a_node_short_of_instances_sends_its_surplus_to_the_host_selector(
        monkeypatch):
    """Two asks that the node's one group serves, on GPU nodes of three
    and four instances: each count column reads three or four free, so
    the scan puts a second placement on a node of three, where the exact
    assignment serves one (each takes two instances). The row loop sent
    that placement to the host selector; the block leaves it out and
    sends it there too, counted, and the selector places it as a row on
    a node of four that has two left, as the row loop's did."""
    from test_inflight_ports import grid_node

    nodes = []
    for i in range(200):
        node = grid_node(i)
        mock.add_nvidia_gpus(node, [_inst(i, j) for j in range(3 + i % 2)])
        nodes.append(node)
    job = gpu_job(300, spread=False)
    job.task_groups[0].tasks[0].resources.devices = [gpu_ask(), gpu_ask()]
    before = REGISTRY.get(SURPLUS)
    block_h, row_h = _both_arms(monkeypatch, nodes, job, "eval-short")
    (plan,) = block_h.plans
    (block,) = plan.alloc_blocks
    surplus = REGISTRY.get(SURPLUS) - before
    assert surplus == 300 - block.size > 0
    assert all(len(block.devices.at(p)[GID]) == 2 for p in range(block.size))
    rows = [a for allocs in plan.node_allocation.values() for a in allocs]
    assert len(rows) == surplus
    assert all(len(a.allocated_devices[GID]) == 2 for a in rows)
    got, want = _by_name(block_h, job), _by_name(row_h, job)
    assert sorted(got) == sorted(want) and len(want) == 300
    for name, row in want.items():
        assert (got[name].node_id, _devices(got[name])) == (
            row.node_id, _devices(row))
    for h in (block_h, row_h):
        _no_instance_twice(h)
        ev = h.evals[-1]
        assert ev.queued_allocations == {"web": 300}
        assert not ev.failed_tg_allocs


def test_surplus_the_host_selector_cannot_place_fails_coalesced(
        monkeypatch):
    """Every GPU node of two instances, the same two asks: the scan
    doubles up on every node once each holds one, and no node has two
    instances left for the selector, so the surplus fails, as the row
    loop's did."""
    nodes = _fleet(400)
    job = gpu_job(300, spread=False)
    job.task_groups[0].tasks[0].resources.devices = [gpu_ask(), gpu_ask()]
    before = REGISTRY.get(SURPLUS)
    block_h, row_h = _both_arms(monkeypatch, nodes, job, "eval-none-left")
    (plan,) = block_h.plans
    (block,) = plan.alloc_blocks
    assert REGISTRY.get(SURPLUS) - before == 300 - block.size == 100
    assert not plan.node_allocation
    got, want = _by_name(block_h, job), _by_name(row_h, job)
    assert sorted(got) == sorted(want) and len(want) == 200
    for h in (block_h, row_h):
        ev = h.evals[-1]
        assert ev.queued_allocations == {"web": 200}
        (failed,) = ev.failed_tg_allocs.values()
        assert failed.coalesced_failures == 99


# -- (c) the applier's device verdict on a block --------------------------


def _inst(n, i):
    return f"gpu-{n}-{i}"


def _gpu_grid():
    g = Grid()
    for n, node in enumerate(g.nodes):
        mock.add_nvidia_gpus(node, [_inst(n, 0), _inst(n, 1)])
    g.store.upsert_nodes(g.nodes)
    return g


def _device_block(g, node_idx, instances, counts=None):
    """A block on `node_idx` whose position p holds `instances[p]`."""
    block = g.block(node_idx, counts=counts)
    block.devices = BlockDevices.of({GID: [i]} for i in instances)
    return block


def _held_row(g, n, instance, index=0):
    a = g.row(n, index=index)
    a.allocated_devices = {GID: [instance]}
    return a


def _collisions():
    return REGISTRY.get("nomad.plan.device_collisions")


def test_a_device_block_meets_a_committed_rows_instance():
    g = _gpu_grid()
    g.store.upsert_allocs([_held_row(g, 0, _inst(0, 0))], ts=1.0)
    before, stats = _collisions(), g.applier.stats["device_collisions"]
    plan = g.plan(blocks=[_device_block(g, [0, 1],
                                        [_inst(0, 0), _inst(1, 0)])])
    result, rejected = assert_parity(g, plan)
    assert rejected == [g.nodes[0].id]
    assert result.alloc_blocks[0].live_node_counts()[0] == [g.nodes[1].id]
    # counted once by the arrays, once again by the parity's exact check
    assert g.applier.stats["device_collisions"] - stats == 2
    assert _collisions() - before == 2


def test_a_device_block_meets_another_blocks_instance():
    g = _gpu_grid()
    g.store.upsert_plan_results([], alloc_blocks=[_device_block(
        g, [0, 1], [_inst(0, 0), _inst(1, 1)])], ts=1.0)
    plan = g.plan(blocks=[_device_block(g, [0, 1],
                                        [_inst(0, 1), _inst(1, 1)])])
    assert assert_parity(g, plan)[1] == [g.nodes[1].id]


@pytest.mark.parametrize("carrier", ["row", "block"])
def test_a_device_block_meets_an_in_flight_results_instance(carrier):
    g = _gpu_grid()
    if carrier == "row":
        flying = PlanResult()
        flying.node_allocation[g.nodes[2].id] = [
            _held_row(g, 2, _inst(2, 1))]
    else:
        flying = PlanResult(alloc_blocks=[_device_block(g, [2],
                                                        [_inst(2, 1)])])
    plan = g.plan(blocks=[_device_block(g, [2, 3],
                                        [_inst(2, 1), _inst(3, 1)])])
    assert assert_parity(g, plan, [flying])[1] == [g.nodes[2].id]
    # ... and once it has landed, the snapshot holds it: the same verdict
    g.store.upsert_plan_results(
        [a for allocs in flying.node_allocation.values() for a in allocs],
        alloc_blocks=list(flying.alloc_blocks), ts=1.0)
    assert assert_parity(g, plan, [flying])[1] == [g.nodes[2].id]


def test_an_in_flight_stop_frees_its_instance_for_a_block():
    g = _gpu_grid()
    held = _held_row(g, 2, _inst(2, 0))
    g.store.upsert_allocs([held], ts=1.0)
    plan = g.plan(blocks=[_device_block(g, [2], [_inst(2, 0)])])
    assert assert_parity(g, plan)[1] == [g.nodes[2].id]
    stop = held.copy_for_update()
    stop.desired_status = enums.ALLOC_DESIRED_STOP
    flying = PlanResult()
    flying.node_update[g.nodes[2].id] = [stop]
    assert assert_parity(g, plan, [flying])[1] == []


@pytest.mark.parametrize("how", ["within_a_row", "two_blocks", "foreign",
                                 "another_nodes"])
def test_an_instance_twice_in_the_plan_or_not_the_nodes_rejects(how):
    g = _gpu_grid()
    if how == "within_a_row":
        blocks = [_device_block(g, [4, 5],
                                [_inst(4, 0), _inst(4, 0), _inst(5, 0)],
                                counts=[2, 1])]
    elif how == "two_blocks":
        blocks = [_device_block(g, [4, 5], [_inst(4, 0), _inst(5, 0)]),
                  _device_block(g, [6, 4], [_inst(6, 0), _inst(4, 0)])]
    elif how == "foreign":
        blocks = [_device_block(g, [4, 5], ["not-an-instance", _inst(5, 0)])]
    else:
        blocks = [_device_block(g, [4, 5], [_inst(5, 1), _inst(5, 0)])]
    stats = g.applier.stats["device_collisions"]
    assert assert_parity(g, g.plan(blocks=blocks))[1] == [g.nodes[4].id]
    assert g.applier.stats["device_collisions"] - stats == 2


def test_a_running_alloc_on_a_dropped_instance_leaves_a_block_node_open():
    """The node no longer has the instance a running allocation holds:
    allocs_fit skips it, and so does the block's verdict."""
    g = _gpu_grid()
    g.store.upsert_allocs([_held_row(g, 0, "dropped")], ts=1.0)
    plan = g.plan(blocks=[_device_block(g, [0], [_inst(0, 0)])])
    assert assert_parity(g, plan)[1] == []


def test_a_disjoint_device_block_passes_without_a_materialised_row(
        monkeypatch):
    g = _gpu_grid()
    g.store.upsert_allocs([_held_row(g, 0, _inst(0, 0))], ts=1.0)
    g.store.upsert_plan_results([], alloc_blocks=[_device_block(
        g, [1], [_inst(1, 0)])], ts=1.0)
    flying = PlanResult(alloc_blocks=[_device_block(g, [2], [_inst(2, 0)])])
    plan = g.plan(blocks=[_device_block(
        g, [0, 1, 2, 3], [_inst(0, 1), _inst(1, 1), _inst(2, 1),
                          _inst(3, 0)])])

    def refused(self, p):
        raise AssertionError("a block position was materialised")

    monkeypatch.setattr(AllocBlock, "alloc_at", refused)
    before = dict(g.applier.stats)
    snap = _OverlaySnapshot(g.store.snapshot(), [flying])
    result, rejected = g.applier._evaluate(snap, plan)
    assert rejected == [] and result.alloc_blocks == plan.alloc_blocks
    assert g.applier.stats["device_collisions"] == before["device_collisions"]
    assert (g.applier.stats["nodes_verified_columnar"]
            - before["nodes_verified_columnar"]) == 4


def test_a_node_the_fit_rejects_is_no_device_collision():
    g = _gpu_grid()
    node = copy.deepcopy(g.nodes[0])
    node.status = enums.NODE_STATUS_DOWN
    g.store.upsert_node(node)
    g.store.upsert_allocs([_held_row(g, 0, _inst(0, 0))], ts=1.0)
    stats = g.applier.stats["device_collisions"]
    plan = g.plan(blocks=[_device_block(g, [0, 1],
                                        [_inst(0, 0), _inst(1, 0)])])
    assert assert_parity(g, plan)[1] == [g.nodes[0].id]
    assert g.applier.stats["device_collisions"] == stats


# -- (d) the store and its readers count a block's instances ----------------


def _dev_usage(store, g, n):
    return store.snapshot().node_dev_usage(g.nodes[n].id)


def _index_used(store, g, n, plan=None):
    ctx = EvalContext(store.snapshot(), plan or Plan(), eval_id="e")
    return ctx.device_index(g.nodes[n]).used.get(GID, set())


@pytest.mark.parametrize("end", ["stop", "gc"])
def test_a_stopped_or_gcd_position_frees_its_instance(end):
    g = _gpu_grid()
    held = _device_block(g, [3], [_inst(3, 0)])
    g.store.upsert_plan_results([], alloc_blocks=[held], ts=1.0)
    assert _dev_usage(g.store, g, 3) == {GID: 1}
    assert _index_used(g.store, g, 3) == {_inst(3, 0)}
    plan = g.plan(blocks=[_device_block(g, [3], [_inst(3, 0)])])
    assert assert_parity(g, plan)[1] == [g.nodes[3].id]
    # the stop promotes the position to a terminal row, its instance
    # with it
    (position,) = g.store.snapshot().allocs_by_node(g.nodes[3].id)
    assert _devices(position) == {GID: [_inst(3, 0)]}
    stop = position.copy_for_update()
    stop.desired_status = enums.ALLOC_DESIRED_STOP
    stop.client_status = enums.ALLOC_CLIENT_COMPLETE
    g.store.upsert_plan_results([], stopped_allocs=[stop], ts=2.0)
    if end == "gc":
        assert g.store.gc_terminal_allocs(g.store.latest_index + 1) == 1
        assert g.store.snapshot().allocs_by_node(g.nodes[3].id) == []
    assert _dev_usage(g.store, g, 3) == {GID: 0}
    assert _index_used(g.store, g, 3) == set()
    assert assert_parity(g, g.plan(blocks=[_device_block(
        g, [3], [_inst(3, 0)])]))[1] == []


def test_proposed_devices_reads_snapshot_plan_and_blocks_unmaterialised(
        monkeypatch):
    g = _gpu_grid()
    g.store.upsert_allocs([_held_row(g, 0, _inst(0, 0))], ts=1.0)
    g.store.upsert_plan_results([], alloc_blocks=[_device_block(
        g, [0, 1], [_inst(0, 1), _inst(1, 0)])], ts=1.0)
    plan = Plan()
    plan.append_block(_device_block(g, [1], [_inst(1, 1)]))
    monkeypatch.setattr(AllocBlock, "alloc_at", lambda self, p: (
        pytest.fail("a block position was materialised")))
    ctx = EvalContext(g.store.snapshot(), plan, eval_id="e")
    assert sorted(ctx.proposed_devices(g.nodes[0].id)) == [
        (GID, _inst(0, 0)), (GID, _inst(0, 1))]
    assert ctx.proposed_devices(g.nodes[1].id) == [
        (GID, _inst(1, 0)), (GID, _inst(1, 1))]
    assert ctx.device_index(g.nodes[1]).assign([gpu_ask()]) is None
    assert _dev_usage(g.store, g, 0) == {GID: 2}


def test_the_kept_columns_and_the_victim_walk_flag_a_device_block():
    """A victim that holds an instance is the host scorer's: the kept
    allocation columns flag a device block's node rows, and so does the
    walk of a node's index entries."""
    from nomad_tpu.tensor.cluster import _walk_node

    g = _gpu_grid()
    g.store.alloc_columns()                 # kept from here on
    plain = g.block([0])
    gpus = _device_block(g, [1], [_inst(1, 0)])
    g.store.upsert_plan_results([], alloc_blocks=[plain, gpus], ts=1.0)
    cols = g.store.alloc_columns()
    for block, flagged in ((plain, False), (gpus, True)):
        (slot,) = cols._blocks[block.id][cols._blocks[block.id] >= 0]
        assert bool(cols.flag[slot]) is flagged
        assert not cols.ports[slot]
    ctx = EvalContext(g.store.snapshot(), Plan(), eval_id="e")
    for n, flagged in ((0, False), (1, True)):
        (cand,), holds = _walk_node(ctx, g.nodes[n].id, set(),
                                    g.job.priority, 100)
        assert cand[3] is flagged and holds is False


# -- (e) every copy of a device block keeps its instances ---------------------


def _committed_device_block():
    g = _gpu_grid()
    block = _device_block(g, [0, 1, 2], [_inst(0, 0), _inst(0, 1),
                                         _inst(1, 0), _inst(2, 1)],
                          counts=[2, 1, 1])
    g.store.upsert_plan_results([], alloc_blocks=[block], job=g.job,
                                ts=1.0)
    return g, block


def _held_by_name(snap):
    return {a.name: (a.node_id, _devices(a)) for a in snap.allocs()}


@pytest.mark.parametrize("how", ["wire", "deepcopy", "without_nodes",
                                 "with_dropped"])
def test_devices_ride_every_copy_of_a_block(how):
    _, block = _committed_device_block()
    if how == "wire":
        copied = wire_decode(json.loads(json.dumps(wire_encode(block))))
    elif how == "deepcopy":
        copied = copy.deepcopy(block)
        assert copied.devices.offsets is not block.devices.offsets
    elif how == "without_nodes":
        copied = block.without_nodes([block.node_ids[0]])
        assert copied.devices_for_row(1) == [(2, [(GID, _inst(1, 0))])]
    else:
        copied = block.with_dropped([1])
        assert copied.devices_for_row(0) == [(0, [(GID, _inst(0, 0))])]
        assert copied.device_counts_for_row(0) == {GID: 1}
    assert copied.devices.groups == [GID]
    assert copied.devices.ids == block.devices.ids
    assert np.array_equal(copied.devices.group, block.devices.group)
    assert np.array_equal(copied.devices.offsets, block.devices.offsets)
    for p in range(4):
        assert _devices(copied.alloc_at(p)) == _devices(block.alloc_at(p))


def test_a_block_without_devices_encodes_as_before():
    g = Grid()
    block = g.block([0, 1])
    fields = wire_encode(block)["__f"]
    assert "devices" not in fields and "ports" not in fields
    assert wire_decode(json.loads(json.dumps(wire_encode(block)))).devices \
        is None
    assert block.alloc_at(0).allocated_devices == {}
    assert copy.deepcopy(block).devices is None
    g.store.upsert_plan_results([], alloc_blocks=[block], ts=1.0)
    snap = g.store.snapshot()
    assert snap.devices_by_node(g.nodes[0].id) == []
    assert snap.node_dev_usage(g.nodes[0].id) is None


def test_a_device_block_survives_a_dump_and_its_restore():
    g, block = _committed_device_block()
    # a stopped position is a terminal row, which holds nothing
    stop = g.store.snapshot().alloc_by_id(f"{block.id}.3").copy_for_update()
    stop.desired_status = enums.ALLOC_DESIRED_STOP
    g.store.upsert_plan_results([], stopped_allocs=[stop], ts=2.0)
    want = _held_by_name(g.store.snapshot())
    dump = json.loads(json.dumps(dump_store(g.store)))
    other = StateStore()
    restore_store(other, dump)
    snap = other.snapshot()
    assert _held_by_name(snap) == want
    assert snap.devices_by_node(g.nodes[0].id) == [
        (GID, _inst(0, 0)), (GID, _inst(0, 1))]
    assert snap.devices_by_node(g.nodes[2].id) == []
    for n, used in ((0, 2), (1, 1), (2, 0)):
        assert (snap.node_dev_usage(g.nodes[n].id) or {}).get(GID, 0) == used
        assert (_dev_usage(g.store, g, n) or {}).get(GID, 0) == used


def test_a_device_block_passes_the_log_into_a_followers_store(tmp_path):
    from nomad_tpu.raft.cluster import RaftCluster

    g, block = _committed_device_block()
    want = _held_by_name(g.store.snapshot())
    with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
        leader = cluster.wait_for_leader(15.0)
        leader.store.upsert_job(g.job)
        leader.store.upsert_plan_results(
            [], alloc_blocks=[copy.deepcopy(block)], job=g.job)
        raft_index = leader.raft.last_applied
        assert '"BlockDevices"' in leader.raft.log.get(raft_index).wire
        for f in cluster.followers():
            f.raft.wait_applied(raft_index, timeout=10.0)
            snap = f.local_store.snapshot()
            assert _held_by_name(snap) == want
            assert snap.devices_by_node(g.nodes[1].id) == [
                (GID, _inst(1, 0))]
            assert snap.node_dev_usage(g.nodes[0].id) == {GID: 2}


# -- a served drain: the deploy kind's guarantee lines over block positions --


def test_a_served_gpu_drain_through_blocks_holds_every_guarantee_line():
    """Two racing jobs of 300 with the documented device block and the
    rack spread through a Server, on 800 nodes of which 400 carry two
    GPUs: every evaluation commits one block, no row is rejected, no
    instance collides, and the plain reference reads every position's
    instances through snap.allocs() (as the deploy kind does) and finds
    every device, capacity and spread line held."""
    from benchmark.deploy.single_agent_gpu import plain_allocs, plain_nodes
    from benchmark.reference import devices as reference
    from nomad_tpu.core.server import Server, ServerConfig
    from nomad_tpu.structs.operator import SchedulerConfiguration
    from test_inflight_ports import live

    fleet = [gpu_grid_node(i, racks=8) for i in range(800)]
    jobs = [gpu_job(300) for _ in range(2)]
    server = Server(ServerConfig(num_workers=2, sched_config=(
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
        assert [len(live(snap, j)) for j in jobs] == [300, 300]
        assert len(list(snap.alloc_blocks())) == 2
        assert not [ev for ev in snap.evals()
                    if ev.status in ("failed", "blocked")]
        stats = server.plan_applier.stats
        assert stats["nodes_rejected"] == 0
        assert stats["device_collisions"] == 0
        assert stats["nodes_verified_columnar"] == stats["nodes_verified"]
        nodes, allocs = plain_nodes(snap), plain_allocs(snap)
        assert reference.census(nodes, allocs) == {
            "allocations": 600, "device_allocations": 600,
            "instances": 600, "nodes": 800, "gpu_nodes": 400}
        assert reference.violations(nodes, allocs) == []
        assert reference.spread_violations(
            nodes, allocs, {j.id for j in jobs}, 2) == []
        # the store's device-usage rows count what the blocks hold
        for node_id, node in nodes.items():
            held = sum(len(ids) for a in allocs if a["node"] == node_id
                       for ids in a["devices"].values())
            row = snap.node_dev_usage(node_id) or {}
            assert sum(row.values()) == held
    finally:
        server.stop()
