"""A group that asks ports leaves the per-placement scan as one
AllocBlock that carries them (`AllocBlock.ports`): a row of numbers a
position, the numbers the hold chose (`TPUPlacer._assign_ports`). The
block must be the row loop's placements node for node and port for
port; the plan applier must hold its numbers to the rule it holds rows'
to (allocs_fit -> check_port_collisions) without materialising an
Allocation; every copy of the block must keep them."""

import copy
import json

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.core.plan_apply import _OverlaySnapshot
from nomad_tpu.state import StateStore
from nomad_tpu.state.persist import dump_store, restore_store
from nomad_tpu.structs import enums
from nomad_tpu.structs.alloc import AllocatedPort, AllocBlock, BlockPorts
from nomad_tpu.structs.plan import PlanResult
from nomad_tpu.structs.resources import NetworkResource
from nomad_tpu.structs.wire import wire_decode, wire_encode
from nomad_tpu.tensor.overlay import INFLIGHT
from nomad_tpu.tensor.placer import TPUPlacer

from test_plan_verify_columnar import Grid, assert_parity
from test_scan_block import _by_name, _drain, _racked_nodes, _spread_job

COLUMNAR = "nomad.placer.columnar_scan_groups"


def _wipe():
    with INFLIGHT._lock:
        INFLIGHT._entries.clear()
        INFLIGHT._ports.clear()
        INFLIGHT._closed.clear()


@pytest.fixture(autouse=True)
def clean_overlay():
    """The overlay is the process's, and a closed entry's ports outlive
    it: no test reads another's."""
    _wipe()
    yield
    _wipe()


def _ports(a):
    return [(p.label, p.value) for p in a.allocated_ports]


def _both_arms(monkeypatch, nodes, job, eval_id):
    """The drain as the block arm makes it, then as the row loop does
    (forced through the arm's test), on the same nodes and seed."""
    block_h = _drain(nodes, job, eval_id)
    _wipe()
    monkeypatch.setattr(TPUPlacer, "_wants_ids",
                        staticmethod(lambda ctx, tg: True))
    row_h = _drain(nodes, job, eval_id)
    return block_h, row_h


# -- (a) parity with the row loop, port for port ------------------------


def test_a_port_block_is_the_row_loops_placements_port_for_port(
        monkeypatch):
    nodes, job = _racked_nodes(200), _spread_job(300, ports=2)
    before = REGISTRY.get(COLUMNAR)
    block_h, row_h = _both_arms(monkeypatch, nodes, job, "eval-ports")
    assert REGISTRY.get(COLUMNAR) == before + 1

    (block_plan,), (row_plan,) = block_h.plans, row_h.plans
    (block,) = block_plan.alloc_blocks
    assert not block_plan.node_allocation and not row_plan.alloc_blocks
    assert block.ports.labels == ["p0", "p1"]
    assert block.ports.values.shape == (300, 2)

    got, want = _by_name(block_h, job), _by_name(row_h, job)
    assert sorted(got) == sorted(want) and len(want) == 300
    for name, row in want.items():
        a = got[name]
        assert a.node_id == row.node_id
        assert _ports(a) == _ports(row)
        assert a.metrics.scores == row.metrics.scores
    # several placements share a node, each with numbers of its own
    per_node = {}
    for a in got.values():
        per_node.setdefault(a.node_id, []).extend(
            p.value for p in a.allocated_ports)
    assert max(map(len, per_node.values())) > 2
    assert all(len(v) == len(set(v)) for v in per_node.values())
    for h in (block_h, row_h):
        assert h.evals[-1].status == enums.EVAL_STATUS_COMPLETE
        assert not h.evals[-1].failed_tg_allocs


# -- (b) a node that runs out of ports -------------------------------------


def _holding(nodes, ports):
    """A harness whose every node already holds `ports` in an
    allocation that the fit's port count does not see (its resource
    vector counts no port), so the scan places more on a node than its
    free port numbers can serve."""
    from nomad_tpu.testing import Harness

    h = Harness()
    h.store.upsert_nodes(copy.deepcopy(nodes))
    holder = mock.job()
    h.store.upsert_job(holder)
    allocs = []
    for node in nodes:
        a = mock.alloc(holder, node)
        a.allocated_vec = a.allocated_vec * 0
        a.allocated_ports = [AllocatedPort(label="x", value=v)
                             for v in ports]
        allocs.append(a)
    h.store.upsert_allocs(allocs)
    return h


def test_a_node_out_of_ports_sends_its_surplus_to_the_coalesced_failure(
        monkeypatch):
    """Ten dynamic port numbers a node, six of them held: the scan's
    count of ten lets five placements of two ports onto a node, and
    _assign_ports runs out after two. The row loop failed the rest one
    by one; the block leaves them out and fails them as one."""
    nodes = _racked_nodes(64, cpu=16000, mem=65536)
    for node in nodes:
        node.resources.max_dynamic_port = 20009
    job = _spread_job(300, ports=2)
    held = range(20000, 20006)
    block_h = _drain(nodes, job, "eval-out", harness=_holding(nodes, held))
    _wipe()
    monkeypatch.setattr(TPUPlacer, "_wants_ids",
                        staticmethod(lambda ctx, tg: True))
    row_h = _drain(nodes, job, "eval-out", harness=_holding(nodes, held))
    (block,) = block_h.plans[0].alloc_blocks
    placed = block.size
    assert placed > 64 and int(block.counts.max()) == 2
    assert set(block.ports.values.ravel().tolist()) == set(
        range(20006, 20010))
    got, want = _by_name(block_h, job), _by_name(row_h, job)
    assert got.keys() == want.keys() and len(got) == placed
    for name, a in got.items():
        assert (a.node_id, _ports(a)) == (want[name].node_id,
                                          _ports(want[name]))
    for h in (block_h, row_h):
        ev = h.evals[-1]
        assert ev.queued_allocations == {"web": placed}
        (failed,) = ev.failed_tg_allocs.values()
        assert failed.coalesced_failures == 300 - placed - 1
        assert failed.dimension_exhausted == {"ports": 1}


# -- (c) the applier's port verdict on a block ---------------------------


def _port_block(g, node_idx, values, counts=None):
    block = g.block(node_idx, counts=counts)
    block.ports = BlockPorts(["http"],
                             np.asarray(values, np.int32).reshape(-1, 1))
    return block


def _held_row(g, node, port, index=0):
    a = g.row(node, index=index)
    a.allocated_ports = [AllocatedPort(label="http", value=port)]
    return a


def _collisions():
    return REGISTRY.get("nomad.plan.port_collisions")


def test_a_port_block_meets_a_committed_rows_port():
    g = Grid()
    g.store.upsert_allocs([_held_row(g, 0, 20000)], ts=1.0)
    before, stats = _collisions(), g.applier.stats["port_collisions"]
    plan = g.plan(blocks=[_port_block(g, [0, 1], [20000, 20000])])
    result, rejected = assert_parity(g, plan)
    assert rejected == [g.nodes[0].id]
    assert result.alloc_blocks[0].live_node_counts()[0] == [g.nodes[1].id]
    # counted once by the arrays, once again by the parity's exact check
    assert g.applier.stats["port_collisions"] - stats == 2
    assert _collisions() - before == 2


def test_a_port_block_meets_another_blocks_port():
    g = Grid()
    g.store.upsert_plan_results(
        [], alloc_blocks=[_port_block(g, [0, 1], [20000, 20001])], ts=1.0)
    plan = g.plan(blocks=[_port_block(g, [0, 1], [20001, 20001])])
    assert assert_parity(g, plan)[1] == [g.nodes[1].id]


@pytest.mark.parametrize("carrier", ["row", "block"])
def test_a_port_block_meets_an_in_flight_results_port(carrier):
    g = Grid()
    if carrier == "row":
        flying = PlanResult()
        flying.node_allocation[g.nodes[2].id] = [_held_row(g, 2, 20005)]
    else:
        flying = PlanResult(alloc_blocks=[_port_block(g, [2], [20005])])
    plan = g.plan(blocks=[_port_block(g, [2, 3], [20005, 20005])])
    assert assert_parity(g, plan, [flying])[1] == [g.nodes[2].id]
    # ... and once it has landed, the snapshot holds it: the same verdict
    g.store.upsert_plan_results(
        [a for allocs in flying.node_allocation.values() for a in allocs],
        alloc_blocks=list(flying.alloc_blocks), ts=1.0)
    assert assert_parity(g, plan, [flying])[1] == [g.nodes[2].id]


@pytest.mark.parametrize("how", ["within_a_row", "two_blocks", "reserved"])
def test_a_port_twice_in_the_plan_or_reserved_rejects_the_node(how):
    g = Grid()
    if how == "within_a_row":
        blocks = [_port_block(g, [4, 5], [20000, 20000, 20001],
                              counts=[2, 1])]
    elif how == "two_blocks":
        blocks = [_port_block(g, [4, 5], [20000, 20001]),
                  _port_block(g, [6, 4], [20000, 20000])]
    else:
        node = copy.deepcopy(g.nodes[4])
        node.reserved.reserved_ports = [20000]
        g.store.upsert_node(node)
        blocks = [_port_block(g, [4, 5], [20000, 20000])]
    assert assert_parity(g, g.plan(blocks=blocks))[1] == [g.nodes[4].id]


def test_a_stopped_position_frees_its_port():
    g = Grid()
    held = _port_block(g, [3], [20000])
    g.store.upsert_plan_results([], alloc_blocks=[held], ts=1.0)
    plan = g.plan(blocks=[_port_block(g, [3], [20000])])
    assert assert_parity(g, plan)[1] == [g.nodes[3].id]
    # the stop promotes the position to a terminal row, its port with it
    (position,) = g.store.snapshot().allocs_by_node(g.nodes[3].id)
    assert _ports(position) == [("http", 20000)]
    stop = position.copy_for_update()
    stop.desired_status = enums.ALLOC_DESIRED_STOP
    g.store.upsert_plan_results([], stopped_allocs=[stop], ts=2.0)
    (row,) = g.store.snapshot().allocs_by_node(g.nodes[3].id)
    assert row.terminal_status() and _ports(row) == [("http", 20000)]
    assert assert_parity(g, g.plan(
        blocks=[_port_block(g, [3], [20000])]))[1] == []


def test_a_disjoint_port_block_passes_without_a_materialised_row(
        monkeypatch):
    g = Grid()
    g.store.upsert_allocs([_held_row(g, 0, 20000)], ts=1.0)
    g.store.upsert_plan_results(
        [], alloc_blocks=[_port_block(g, [1], [20000])], ts=1.0)
    flying = PlanResult(alloc_blocks=[_port_block(g, [2], [20000])])
    plan = g.plan(blocks=[_port_block(g, [0, 1, 2, 3],
                                      [20001, 20001, 20001, 20000])])

    def refused(self, p):
        raise AssertionError("a block position was materialised")

    monkeypatch.setattr(AllocBlock, "alloc_at", refused)
    before = dict(g.applier.stats)
    snap = _OverlaySnapshot(g.store.snapshot(), [flying])
    result, rejected = g.applier._evaluate(snap, plan)
    assert rejected == [] and result.alloc_blocks == plan.alloc_blocks
    assert g.applier.stats["port_collisions"] == before["port_collisions"]
    assert (g.applier.stats["nodes_verified_columnar"]
            - before["nodes_verified_columnar"]) == 4


def test_a_node_the_fit_rejects_is_no_port_collision():
    g = Grid()
    node = copy.deepcopy(g.nodes[0])
    node.status = enums.NODE_STATUS_DOWN
    g.store.upsert_node(node)
    g.store.upsert_allocs([_held_row(g, 0, 20000)], ts=1.0)
    stats = g.applier.stats["port_collisions"]
    plan = g.plan(blocks=[_port_block(g, [0, 1], [20000, 20000])])
    assert assert_parity(g, plan)[1] == [g.nodes[0].id]
    assert g.applier.stats["port_collisions"] == stats


# -- (d) every copy of a port block keeps its ports ------------------------


def _committed_port_block():
    g = Grid()
    block = _port_block(g, [0, 1, 2], [20000, 20001, 20002, 20003],
                        counts=[2, 1, 1])
    g.store.upsert_plan_results([], alloc_blocks=[block], job=g.job,
                                ts=1.0)
    return g, block


def _held_by_name(snap):
    return {a.name: (a.node_id, _ports(a)) for a in snap.allocs()}


@pytest.mark.parametrize("how", ["wire", "deepcopy", "without_nodes",
                                 "with_dropped"])
def test_ports_ride_every_copy_of_a_block(how):
    _, block = _committed_port_block()
    if how == "wire":
        copied = wire_decode(json.loads(json.dumps(wire_encode(block))))
    elif how == "deepcopy":
        copied = copy.deepcopy(block)
        assert copied.ports.values is not block.ports.values
    elif how == "without_nodes":
        copied = block.without_nodes([block.node_ids[0]])
        assert copied.ports_for_row(1) == [(2, [20002])]
    else:
        copied = block.with_dropped([1])
        assert copied.ports_for_row(0) == [(0, [20000])]
    assert copied.ports.labels == ["http"]
    assert copied.ports.values.dtype == np.int32
    assert np.array_equal(copied.ports.values, block.ports.values)
    for p in range(4):
        assert _ports(copied.alloc_at(p)) == _ports(block.alloc_at(p))


def test_a_portless_block_encodes_as_before():
    g = Grid()
    block = g.block([0, 1])
    fields = wire_encode(block)["__f"]
    assert "ports" not in fields
    assert wire_decode(json.loads(json.dumps(wire_encode(block)))).ports \
        is None
    assert block.alloc_at(0).allocated_ports == []
    assert copy.deepcopy(block).ports is None
    assert g.store.snapshot().ports_by_node(g.nodes[0].id) == []


def test_a_port_block_survives_a_dump_and_its_restore():
    g, _ = _committed_port_block()
    want = _held_by_name(g.store.snapshot())
    assert sorted(p for _, ps in want.values() for _, p in ps) == [
        20000, 20001, 20002, 20003]
    dump = json.loads(json.dumps(dump_store(g.store)))
    other = StateStore()
    restore_store(other, dump)
    snap = other.snapshot()
    assert _held_by_name(snap) == want
    assert snap.ports_by_node(g.nodes[0].id) == [20000, 20001]


def test_a_port_block_passes_the_log_into_a_followers_store(tmp_path):
    from nomad_tpu.raft.cluster import RaftCluster

    g, block = _committed_port_block()
    want = _held_by_name(g.store.snapshot())
    with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
        leader = cluster.wait_for_leader(15.0)
        leader.store.upsert_job(g.job)
        leader.store.upsert_plan_results(
            [], alloc_blocks=[copy.deepcopy(block)], job=g.job)
        raft_index = leader.raft.last_applied
        assert '"ports"' in leader.raft.log.get(raft_index).wire
        for f in cluster.followers():
            f.raft.wait_applied(raft_index, timeout=10.0)
            snap = f.local_store.snapshot()
            assert _held_by_name(snap) == want
            assert snap.ports_by_node(g.nodes[1].id) == [20002]


def test_a_portless_block_rides_the_log_without_the_field(tmp_path):
    from nomad_tpu.raft.cluster import RaftCluster

    g = Grid()
    block = g.block([0, 1])
    with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
        leader = cluster.wait_for_leader(15.0)
        leader.store.upsert_job(g.job)
        leader.store.upsert_plan_results([], alloc_blocks=[block], job=g.job)
        entry = leader.raft.log.get(leader.raft.last_applied)
        assert '"AllocBlock"' in entry.wire and '"ports"' not in entry.wire


# -- a served drain: the deploy kind's port line over block positions -------


def test_a_served_port_drain_commits_blocks_that_the_reference_holds():
    """Two racing jobs of 300 with two ports a task through a Server:
    every evaluation commits one block, no row is rejected, and the
    plain reference reads every position's ports through snap.allocs()
    (as the deploy kind does) and finds nothing wrong."""
    from benchmark.deploy.single_agent_ports import plain_allocs, plain_nodes
    from benchmark.reference import ports as reference
    from nomad_tpu.core.server import Server, ServerConfig
    from nomad_tpu.structs.operator import SchedulerConfiguration
    from test_inflight_ports import grid_job, grid_node, live

    fleet = [grid_node(i) for i in range(320)]
    jobs = [grid_job(300) for _ in range(2)]
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
        stats = server.plan_applier.stats
        assert stats["nodes_rejected"] == 0 and stats["port_collisions"] == 0
        assert stats["nodes_verified_columnar"] == stats["nodes_verified"]
        nodes, allocs = plain_nodes(snap), plain_allocs(snap)
        assert reference.violations(nodes, allocs) == []
        assert reference.census(nodes, allocs)["ports"] == 1200
    finally:
        server.stop()


def test_a_port_job_without_a_spread_leaves_the_scan_as_a_block():
    """No spread, so BestFit alone: the count solve takes no port, the
    scan does, and hands its placements over as one block whose every
    position holds a number in its node's dynamic range."""
    nodes = _racked_nodes(64, cpu=16000, mem=65536)
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = 300
    tg.tasks[0].resources.networks = [NetworkResource(
        dynamic_ports=["http"])]
    before = REGISTRY.get(COLUMNAR)
    h = _drain(nodes, job, "eval-binpack")
    assert REGISTRY.get(COLUMNAR) == before + 1
    (block,) = h.plans[0].alloc_blocks
    assert block.size == 300 and block.ports.labels == ["http"]
    lo, hi = (nodes[0].resources.min_dynamic_port,
              nodes[0].resources.max_dynamic_port)
    assert ((block.ports.values >= lo) & (block.ports.values <= hi)).all()
