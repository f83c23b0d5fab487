"""The plan applier's array fit check (`PlanApplier._columnar_verdicts`)
against `_node_plan_valid`, the exact check a node: the same verdict for
every node of every plan. The exact check walks the node's allocations
(an in-flight block's rows materialised), so it shares nothing with the
arrays but the store.
"""

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.core.plan_apply import (PlanApplier, PlanQueue,
                                       _OverlaySnapshot)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import DrainStrategy, enums
from nomad_tpu.structs.alloc import AllocatedPort, AllocBlock
from nomad_tpu.structs.plan import Plan, PlanResult
from nomad_tpu.structs.resources import RESOURCE_DIMS, R_PORTS

CPU, MEM = 14000, 32000
DIMS = ["cpu", "memory", "disk", "ports"]


class Grid:
    """A small cluster, one job, and the makers of what plans carry."""

    def __init__(self, n_nodes=8):
        self.store = StateStore()
        self.nodes = []
        for _ in range(n_nodes):
            n = mock.node()
            n.resources.cpu, n.resources.memory_mb = CPU, MEM
            n.compute_class()
            self.nodes.append(n)
        self.store.upsert_nodes(self.nodes)
        self.job = mock.job()
        self.store.upsert_job(self.job)
        self.applier = PlanApplier(self.store, PlanQueue())
        self._blocks = 0

    def vec(self, cpu=6000.0, mem=6000.0, disk=0.0, ports=0.0):
        v = np.zeros(RESOURCE_DIMS)
        v[:] = cpu, mem, disk, ports
        return v

    def block(self, node_idx, counts=None, vec=None):
        self._blocks += 1
        nodes = [self.nodes[i] for i in node_idx]
        counts = np.asarray(counts if counts is not None
                            else [1] * len(nodes), dtype=np.int64)
        return AllocBlock(
            id=f"blk-{self._blocks}", eval_id="ev", job_id=self.job.id,
            job=self.job, task_group="web",
            name_indices=np.arange(int(counts.sum())),
            node_ids=[n.id for n in nodes],
            node_names=[n.name for n in nodes], counts=counts,
            allocated_vec=self.vec() if vec is None else vec)

    def row(self, node_idx, vec=None, index=0):
        a = mock.alloc(self.job, self.nodes[node_idx], index=index)
        a.allocated_vec = self.vec() if vec is None else vec
        return a

    def plan(self, blocks=(), rows=(), **kw):
        plan = Plan(eval_id="e", snapshot_index=self.store.latest_index, **kw)
        for b in blocks:
            plan.append_block(b)
        for a in rows:
            plan.append_alloc(a)
        return plan

    def free(self, node_idx):
        snap = self.store.snapshot()
        node = snap.node_by_id(self.nodes[node_idx].id)
        used = snap.node_usage(node.id)
        return node.available_vec() - (0.0 if used is None else used)


def exact_rejections(applier, snap, plan):
    """What `_node_plan_valid` says of every node the plan touches."""
    nodes = set(plan.node_allocation) | set(plan.node_update) \
        | set(plan.node_preemptions)
    for b in plan.alloc_blocks:
        nodes.update(b.live_node_counts()[0])
    return sorted(n for n in nodes
                  if not applier._node_plan_valid(snap, plan, n))


def assert_parity(grid, plan, overlays=(), snap=None):
    """Run `_evaluate` (arrays for what qualifies) and the exact check a
    node on the same view; return (result, rejected)."""
    snap = snap if snap is not None else grid.store.snapshot()
    view = _OverlaySnapshot(snap, list(overlays)) if overlays else snap
    want = exact_rejections(grid.applier, view, plan)
    result, rejected = grid.applier._evaluate(view, plan)
    if plan.all_at_once and want:
        assert not result.alloc_blocks and not result.node_allocation
        return result, rejected
    assert rejected == want
    live = {nid for b in result.alloc_blocks
            for nid in b.live_node_counts()[0]}
    assert not live & set(want)
    assert not set(result.node_allocation) & set(want)
    return result, rejected


# -- what a plan carries ----------------------------------------------------


def test_blocks_only():
    g = Grid()
    plan = g.plan(blocks=[g.block([0, 1, 2], counts=[2, 1, 3])])
    result, rejected = assert_parity(g, plan)
    assert rejected == [g.nodes[2].id]           # three of the task: 18000
    assert result.alloc_blocks[0].rejected_rows == frozenset({2})
    assert result.alloc_blocks[0].live_size() == 3


def test_a_plan_no_node_of_which_is_rejected_keeps_its_blocks_as_they_are():
    g = Grid()
    blocks = [g.block([0, 1]), g.block([1, 2])]
    result, rejected = assert_parity(g, g.plan(blocks=blocks))
    assert rejected == []
    assert [id(b) for b in result.alloc_blocks] == [id(b) for b in blocks]


def test_rows_only():
    g = Grid()
    rows = [g.row(0), g.row(0, index=1), g.row(1, index=2),
            g.row(2, vec=g.vec(cpu=15000.0), index=3)]
    result, rejected = assert_parity(g, g.plan(rows=rows))
    assert rejected == [g.nodes[2].id]
    assert set(result.node_allocation) == {g.nodes[0].id, g.nodes[1].id}


def test_a_block_and_rows_on_one_node():
    g = Grid()
    fits = g.plan(blocks=[g.block([0, 1])], rows=[g.row(0)])
    assert assert_parity(g, fits)[1] == []
    over = g.plan(blocks=[g.block([0, 1], counts=[2, 1])], rows=[g.row(0)])
    assert assert_parity(g, over)[1] == [g.nodes[0].id]


def test_two_blocks_and_a_node_twice_in_one_block():
    g = Grid()
    twice = g.block([0, 0, 1])                       # 2 on node 0, 1 on node 1
    assert assert_parity(g, g.plan(blocks=[twice, g.block([1])]))[1] == []
    assert assert_parity(
        g, g.plan(blocks=[twice, g.block([0, 1])]))[1] == [g.nodes[0].id]


@pytest.mark.parametrize("carrier", ["block", "row"])
@pytest.mark.parametrize("over", [0.0, 1.0], ids=["exact_fit", "one_over"])
@pytest.mark.parametrize("dim", range(RESOURCE_DIMS), ids=DIMS)
def test_an_exact_fit_and_one_unit_over_it(dim, over, carrier):
    g = Grid()
    g.store.upsert_allocs([g.row(0, vec=g.vec(3000.0, 5000.0, 70.0, 2.0))],
                          ts=1.0)
    ask = g.vec(100.0, 100.0, 10.0, 0.0)
    ask[dim] = g.free(0)[dim] + over
    plan = (g.plan(blocks=[g.block([0, 1], vec=ask)])
            if carrier == "block" else g.plan(rows=[g.row(0, vec=ask)]))
    _, rejected = assert_parity(g, plan)
    # node 1 is empty: a whole node's worth of one dimension fits it
    assert rejected == ([g.nodes[0].id] if over else [])


def _down(g):
    g.store.update_node_status(g.nodes[1].id, enums.NODE_STATUS_DOWN, ts=1.0)


def _draining(g):
    g.store.update_node_drain(g.nodes[1].id, DrainStrategy())


def _deleted(g):
    g.store.delete_node(g.nodes[1].id)


def _unknown(g):
    g.nodes[1] = mock.node()        # never registered


@pytest.mark.parametrize("carrier", ["block", "row"])
@pytest.mark.parametrize("make", [_down, _draining, _deleted, _unknown],
                         ids=lambda f: f.__name__[1:])
def test_a_node_that_may_take_no_placement_rejects(make, carrier):
    g = Grid()
    make(g)
    plan = (g.plan(blocks=[g.block([0, 1, 2])]) if carrier == "block"
            else g.plan(rows=[g.row(i, index=i) for i in range(3)]))
    assert assert_parity(g, plan)[1] == [g.nodes[1].id]


def test_an_ineligible_node_takes_what_was_planned_for_it():
    g = Grid()
    g.store.update_node_eligibility(g.nodes[1].id,
                                    enums.NODE_SCHED_INELIGIBLE)
    assert assert_parity(g, g.plan(blocks=[g.block([0, 1])]))[1] == []


def test_nodes_with_stops_or_ports_keep_the_exact_check_beside_the_arrays():
    """A node whose plan frees room with a stop is judged by the exact
    check, the block's row on it included; its neighbours by arrays."""
    g = Grid()
    old = [g.row(0, index=0), g.row(0, index=1)]           # node 0 is full
    g.store.upsert_allocs(old, ts=1.0)
    plan = g.plan(blocks=[g.block([0, 1, 2])])
    assert assert_parity(g, plan)[1] == [g.nodes[0].id]
    plan.append_stopped_alloc(old[0], "make room")
    ported = g.row(3, index=9)
    ported.allocated_ports = [AllocatedPort(label="http", value=20000)]
    plan.append_alloc(ported)
    before = dict(g.applier.stats)
    assert assert_parity(g, plan)[1] == []
    assert g.applier.stats["nodes_verified"] - before["nodes_verified"] == 4
    assert (g.applier.stats["nodes_verified_columnar"]
            - before["nodes_verified_columnar"]) == 2


def test_a_block_with_no_live_row_beside_a_node_of_the_exact_check():
    """What preemption plans look like once the applier has cut every
    row of their block: nothing is left for the arrays to judge."""
    g = Grid()
    old = g.row(0)
    g.store.upsert_allocs([old], ts=1.0)
    spent = g.block([1, 2]).without_nodes([g.nodes[1].id, g.nodes[2].id])
    plan = g.plan(blocks=[spent], rows=[g.row(0, index=1)])
    plan.append_stopped_alloc(old, "make room")
    before = dict(g.applier.stats)
    result, rejected = assert_parity(g, plan)
    assert rejected == [] and result.alloc_blocks == []
    assert g.applier.stats["nodes_verified"] - before["nodes_verified"] == 1
    assert (g.applier.stats["nodes_verified_columnar"]
            == before["nodes_verified_columnar"])


# -- results in flight -------------------------------------------------------


def _in_flight(g, n):
    """n results in flight on nodes 0..3, the first with a row beside
    its block, the second with a stop: one task's worth each a node."""
    results = []
    for j in range(n):
        r = PlanResult(alloc_blocks=[g.block([j, j + 1])])
        results.append(r)
    if n > 0:
        results[0].node_allocation[g.nodes[5].id] = [g.row(5, index=50)]
    return results


@pytest.mark.parametrize("n", [0, 1, 2, 3])
def test_in_flight_results_count_once(n):
    g = Grid()
    overlays = _in_flight(g, n)
    # one more task a node on nodes 0..5: a node two results share is full
    plan = g.plan(blocks=[g.block([0, 1, 2, 3, 4])], rows=[g.row(5)])
    _, rejected = assert_parity(g, plan, overlays)
    shared = {1: [], 2: [1], 3: [1, 2]}.get(n, [])
    assert rejected == sorted(g.nodes[i].id for i in shared)


def _summed_in_the_accumulator(g):
    """Whether this thread's verifies so far went through the sparse
    accumulator (the general way) at all."""
    return getattr(g.applier._scratch, "acc", None) is not None


def test_one_block_that_meets_nothing_in_flight_is_judged_without_sums():
    """The common case has a way of its own: what the block asks of
    its nodes is all that is asked of them. Anything else is summed."""
    g = Grid()
    overlays = _in_flight(g, 2)                 # blocks on nodes 0..2
    own = g.plan(blocks=[g.block([3, 4, 6], counts=[2, 3, 1])])
    assert assert_parity(g, own, overlays[1:])[1] == [g.nodes[4].id]
    assert not _summed_in_the_accumulator(g)
    meets = g.plan(blocks=[g.block([2, 3], counts=[2, 1])])
    assert assert_parity(g, meets, overlays[1:])[1] == [g.nodes[2].id]
    assert _summed_in_the_accumulator(g)


@pytest.mark.parametrize("what", ["a_row_in_flight", "two_blocks",
                                  "a_node_twice", "a_row_beside_it"])
def test_what_takes_a_block_to_the_accumulator(what):
    g = Grid()
    overlays, blocks, rows = [], [g.block([3, 4])], []
    if what == "a_row_in_flight":
        overlays = _in_flight(g, 1)             # a row on node 5 beside it
    elif what == "two_blocks":
        blocks.append(g.block([6, 7]))
    elif what == "a_node_twice":
        blocks = [g.block([3, 3, 4])]
    else:
        rows = [g.row(6)]
    assert assert_parity(g, g.plan(blocks=blocks, rows=rows),
                         overlays)[1] == []
    assert _summed_in_the_accumulator(g)
    # and the accumulator is left as it was found: zeros
    assert not g.applier._scratch.acc.any()


def test_a_result_that_landed_before_the_snapshot_is_not_added_again():
    """PR 34's double count: the applier lists the results in flight,
    then takes its snapshot; a commit landing between the two is in
    both, and its nodes read twice as full."""
    g = Grid()
    landed, flying = _in_flight(g, 2)
    g.store.upsert_plan_results(
        [a for allocs in landed.node_allocation.values() for a in allocs],
        alloc_blocks=list(landed.alloc_blocks), ts=1.0)
    plan = g.plan(blocks=[g.block([0, 1, 2])], rows=[g.row(5)])
    # node 1 holds landed's and flying's: full; 0, 2 and 5 have room for
    # one more only if landed is counted once
    assert assert_parity(g, plan, [landed, flying])[1] == [g.nodes[1].id]


def test_a_result_that_lands_after_the_snapshot_is_still_added_once():
    """The other side of the same race: the snapshot does not hold the
    result, the dense matrix soon does. The usage is read at the
    snapshot's generation (a node at a time once the store has moved
    on), where "already holds" was decided, so it counts once."""
    g = Grid()
    flying = _in_flight(g, 1)[0]
    snap = g.store.snapshot()
    view = _OverlaySnapshot(snap, [flying])
    g.store.upsert_plan_results(
        [a for allocs in flying.node_allocation.values() for a in allocs],
        alloc_blocks=list(flying.alloc_blocks), ts=1.0)
    assert g.store.latest_index > snap.index
    plan = g.plan(blocks=[g.block([0, 1, 2])], rows=[g.row(5)])
    result, rejected = g.applier._evaluate(view, plan)
    assert rejected == exact_rejections(g.applier, view, plan) == []
    # and two more of the task on those nodes no longer fit, either way
    plan = g.plan(blocks=[g.block([0, 2], counts=[2, 2])])
    result, rejected = g.applier._evaluate(view, plan)
    assert rejected == exact_rejections(g.applier, view, plan) \
        == [g.nodes[0].id]


def test_an_in_flight_stop_frees_room_for_the_array_path():
    g = Grid()
    old = [g.row(0, index=0), g.row(0, index=1)]
    g.store.upsert_allocs(old, ts=1.0)
    stopping = PlanResult()
    stopped = old[0].copy_for_update()
    stopped.desired_status = enums.ALLOC_DESIRED_STOP
    stopping.node_update[g.nodes[0].id] = [stopped]
    plan = g.plan(blocks=[g.block([0, 1])])
    assert assert_parity(g, plan)[1] == [g.nodes[0].id]
    assert assert_parity(g, plan, [stopping])[1] == []


def test_verify_through_the_applier_reads_the_newest_generation():
    g = Grid()
    flying = _in_flight(g, 1)[0]
    plan = g.plan(blocks=[g.block([0, 1], counts=[2, 2])])
    result, rejected = g.applier._verify(plan, [flying])
    assert rejected == sorted([g.nodes[0].id, g.nodes[1].id])
    assert result.alloc_blocks == []


# -- all_at_once, the counters, the tracker ---------------------------------


def test_all_at_once_rejects_every_node_of_a_plan_with_one_bad_node():
    g = Grid()
    plan = g.plan(blocks=[g.block([0, 1], counts=[1, 3])], rows=[g.row(2)],
                  all_at_once=True)
    result, rejected = assert_parity(g, plan)
    assert rejected == sorted(n.id for n in g.nodes[:3])
    assert result.deployment is None
    fits = g.plan(blocks=[g.block([0, 1])], rows=[g.row(2)],
                  all_at_once=True)
    result, rejected = assert_parity(g, fits)
    assert rejected == [] and len(result.alloc_blocks) == 1


def test_the_counters_count_what_they_counted():
    g = Grid()
    registry = REGISTRY.dump()
    plan = g.plan(blocks=[g.block([0, 1, 2], counts=[1, 1, 3])],
                  rows=[g.row(3), g.row(3, index=1), g.row(3, index=2)])
    for _ in range(2):          # a re-verified plan's rows count again
        _, rejected = g.applier._evaluate(g.store.snapshot(), plan)
        assert rejected == sorted([g.nodes[2].id, g.nodes[3].id])
    assert g.applier.stats["nodes_verified"] == 8
    assert g.applier.stats["nodes_verified_columnar"] == 8
    assert g.applier.stats["nodes_rejected"] == 0     # _finalize's, once
    after = REGISTRY.dump()
    for name, n in (("nomad.plan.nodes_verified", 8),
                    ("nomad.plan.nodes_verified_columnar", 8)):
        assert after[name] - registry.get(name, 0) == n


def test_a_node_the_arrays_reject_feeds_the_bad_node_tracker():
    g = Grid()
    reported = []
    g.applier.bad_nodes.threshold = 3
    g.applier.bad_nodes.on_bad_node = reported.append
    plan = g.plan(blocks=[g.block([0, 1], counts=[1, 3])])
    for _ in range(3):
        g.applier._evaluate(g.store.snapshot(), plan)
    assert reported == [g.nodes[1].id]
    assert g.applier.bad_nodes.stats["bad_nodes"] == 1


# -- generated plans ---------------------------------------------------------


@pytest.mark.parametrize("seed", range(12))
def test_generated_plans_get_the_exact_checks_verdicts(seed):
    rng = np.random.default_rng(seed)
    g = Grid(n_nodes=12)
    # some usage to start from, and a node of each kind that takes nothing
    g.store.upsert_allocs(
        [g.row(int(i), vec=g.vec(float(rng.integers(1, 9)) * 1000.0,
                                 float(rng.integers(1, 9)) * 1000.0),
               index=int(k))
         for k, i in enumerate(rng.integers(0, 12, size=8))], ts=1.0)
    g.store.update_node_status(g.nodes[10].id, enums.NODE_STATUS_DOWN,
                               ts=2.0)
    g.store.update_node_drain(g.nodes[11].id, DrainStrategy())

    def some_block():
        idx = rng.choice(12, size=int(rng.integers(1, 7)), replace=False)
        return g.block(idx.tolist(),
                       counts=rng.integers(1, 3, size=len(idx)),
                       vec=g.vec(float(rng.integers(1, 5)) * 1000.0,
                                 float(rng.integers(1, 5)) * 2000.0))

    overlays = []
    for _ in range(int(rng.integers(0, 4))):
        r = PlanResult(alloc_blocks=[some_block()])
        if rng.random() < 0.5:
            i = int(rng.integers(0, 12))
            r.node_allocation[g.nodes[i].id] = [g.row(i, index=70)]
        overlays.append(r)
    if overlays and rng.random() < 0.5:      # the first has landed already
        first = overlays[0]
        g.store.upsert_plan_results(
            [a for allocs in first.node_allocation.values() for a in allocs],
            alloc_blocks=list(first.alloc_blocks), ts=3.0)
    for _ in range(6):
        plan = g.plan(
            blocks=[some_block() for _ in range(int(rng.integers(0, 3)))],
            rows=[g.row(int(i), vec=g.vec(float(rng.integers(1, 8)) * 1000.0,
                                          1000.0), index=80 + k)
                  for k, i in enumerate(
                      rng.integers(0, 12, size=int(rng.integers(0, 5))))])
        assert_parity(g, plan, overlays)


def test_the_port_dimension_is_a_count_like_the_others():
    g = Grid()
    slots = g.free(0)[R_PORTS]
    fits = g.plan(blocks=[g.block([0], counts=[2],
                                  vec=g.vec(10.0, 10.0, 0.0, slots / 2))])
    assert assert_parity(g, fits)[1] == []
    over = g.plan(blocks=[g.block([0], counts=[2],
                                  vec=g.vec(10.0, 10.0, 0.0, slots / 2 + 1))])
    assert assert_parity(g, over)[1] == [g.nodes[0].id]
