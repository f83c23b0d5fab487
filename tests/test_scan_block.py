"""The per-placement tier hands its placements over as one AllocBlock
(PR 34): a BulkPlacementRequest whose spread / distinct_hosts rules out
the count solve stays columnar through the scan. The launch is the row
loop's, so every observable result must be the row loop's too; the
plan's own blocks must be visible to the evaluation's later groups."""

import copy

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import (BulkPlacementRequest,
                                           PlacementRequest)
from nomad_tpu.structs import Constraint, Spread, enums
from nomad_tpu.structs.alloc import AllocBlock, alloc_name
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.plan import Plan, PlanResult
from nomad_tpu.structs.resources import NetworkResource
from nomad_tpu.structs.wire import wire_decode, wire_encode
from nomad_tpu.tensor.cluster import ClusterTensors
from nomad_tpu.tensor.overlay import INFLIGHT
from nomad_tpu.tensor.placer import TPUPlacer
from nomad_tpu.testing import Harness

COLUMNAR = "nomad.placer.columnar_scan_groups"
STAGED = "nomad.placer.staged_solves"


def _cfg():
    return SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)


def _racked_nodes(n: int, racks: int = 5, cpu: int = 4000,
                  mem: int = 8192):
    nodes = []
    for i in range(n):
        node = mock.node()
        node.meta["rack"] = f"r{i % racks}"
        node.resources.cpu, node.resources.memory_mb = cpu, mem
        node.compute_class()
        nodes.append(node)
    return nodes


def _spread_job(count: int = 300, ports: int = 0):
    """The grid's shape: one group of fresh placements with a spread on
    the rack and (ports=0) no port, device or core."""
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    tg.spreads = [Spread(attribute="${meta.rack}", weight=50)]
    tg.tasks[0].resources.networks = (
        [NetworkResource(dynamic_ports=[f"p{i}" for i in range(ports)])]
        if ports else [])
    return job


def _drain(nodes, job, eval_id: str, cfg=None, harness=None):
    h = harness or Harness()
    h.store.upsert_nodes(copy.deepcopy(nodes))
    job = copy.deepcopy(job)
    h.store.upsert_job(job)
    ev = mock.eval_for(job)
    ev.id = eval_id        # seeds the tie-break permutation
    INFLIGHT._entries.clear()
    h.process(ev, sched_config=cfg or _cfg())
    return h


def _by_name(h, job):
    return {a.name: a for a in h.store.snapshot().allocs_by_job(job.id)}


# -- (a) parity with the row loop ------------------------------------


def test_block_arm_places_what_the_row_loop_places(monkeypatch):
    nodes, job = _racked_nodes(200), _spread_job(300)
    before = REGISTRY.get(COLUMNAR)
    block_h = _drain(nodes, job, "eval-parity")
    assert REGISTRY.get(COLUMNAR) == before + 1
    monkeypatch.setattr(TPUPlacer, "_wants_exact_ids",
                        staticmethod(lambda ctx, tg: True))
    row_h = _drain(nodes, job, "eval-parity")
    assert REGISTRY.get(COLUMNAR) == before + 1

    (block_plan,), (row_plan,) = block_h.plans, row_h.plans
    assert len(block_plan.alloc_blocks) == 1
    assert not block_plan.node_allocation
    assert not row_plan.alloc_blocks
    assert sum(map(len, row_plan.node_allocation.values())) == 300

    got, want = _by_name(block_h, job), _by_name(row_h, job)
    assert sorted(got) == sorted(want) == sorted(
        alloc_name(job.id, "web", i) for i in range(300))
    for name, row in want.items():
        a = got[name]
        # the same launch: every placement on the row loop's node, with
        # the AllocMetric the row loop wrote
        assert a.node_id == row.node_id and a.node_name == row.node_name
        assert a.metrics.scores == row.metrics.scores
        assert list(a.metrics.scores) == [f"{a.node_id}.normalized-score"]
        assert (a.metrics.nodes_evaluated == row.metrics.nodes_evaluated
                == 200)
        assert a.metrics.nodes_in_pool == row.metrics.nodes_in_pool == 200
        assert np.array_equal(a.allocated_vec, row.allocated_vec)
        assert (a.eval_id, a.job_version, a.deployment_id != "") == (
            row.eval_id, row.job_version, row.deployment_id != "")
    block_ev, row_ev = block_h.evals[-1], row_h.evals[-1]
    assert block_ev.status == row_ev.status == enums.EVAL_STATUS_COMPLETE
    assert block_ev.queued_allocations == row_ev.queued_allocations
    assert not block_ev.failed_tg_allocs and not row_ev.failed_tg_allocs
    # the spread holds through the block as through the rows
    racks = {n.id: n.meta["rack"] for n in nodes}
    per_rack = [np.bincount([int(racks[a.node_id][1:])
                             for a in arm.values()], minlength=5)
                for arm in (got, want)]
    assert np.array_equal(*per_rack) and per_rack[0].min() > 50


def test_block_positions_follow_their_nodes_in_stable_order():
    nodes, job = _racked_nodes(64, cpu=8000), _spread_job(300)
    h = _drain(nodes, job, "eval-order")
    (block,) = h.plans[0].alloc_blocks
    assert block.size == 300 == int(block.counts.sum())
    assert len(set(block.node_ids)) == len(block.node_ids)
    assert len(block.scores) == 300 and block.scores.dtype.kind == "f"
    assert sorted(block.name_indices.tolist()) == list(range(300))
    index = {n.id: i for i, n in enumerate(
        ClusterTensors.build(EvalContext(h.store.snapshot()),
                             h.store.snapshot().ready_nodes_in_pool(
                                 job.datacenters, job.node_pool)).nodes)}
    order = [index[nid] for nid in block.node_ids]
    assert order == sorted(order)
    for m in range(len(block.node_ids)):
        # within a node, placements keep the order the scan made them in
        idx = block.name_indices[list(block.positions_for_row(m))]
        assert idx.tolist() == sorted(idx.tolist())
    assert block.mean_score == pytest.approx(float(block.scores.mean()))


# -- (b) the evaluation's own plan sees its blocks ---------------------


def _two_group_job(count: int = 300):
    job = _spread_job(count)
    second = copy.deepcopy(job.task_groups[0])
    second.name = "api"
    job.task_groups.append(second)
    return job


def test_second_group_sees_the_first_groups_block(monkeypatch):
    """Group B's usage gather and placement counts carry group A's
    block: on the parent `touched` and `placement_counts` walked
    node_allocation only."""
    nodes = _racked_nodes(640, cpu=1500, mem=4096)
    job = _two_group_job(300)
    job.constraints = [Constraint(operand=enums.CONSTRAINT_DISTINCT_HOSTS)]
    for tg in job.task_groups:
        tg.tasks[0].resources.cpu = 500
        tg.tasks[0].resources.memory_mb = 256

    gathers, counted = [], []
    refresh, counts = (ClusterTensors.refresh_usage,
                       ClusterTensors.placement_counts)

    def spy_refresh(self, ctx, out=None):
        refresh(self, ctx, out=out)
        if out is not None:
            gathers.append((out.copy(), list(ctx.plan.alloc_blocks),
                            dict(self.node_index)))

    def spy_counts(self, job, tg, ctx):
        ptg, pjob = counts(self, job, tg, ctx)
        counted.append((tg.name, ptg.copy(), pjob.copy()))
        return ptg, pjob

    monkeypatch.setattr(ClusterTensors, "refresh_usage", spy_refresh)
    monkeypatch.setattr(ClusterTensors, "placement_counts", spy_counts)
    before = REGISTRY.get(COLUMNAR)
    h = _drain(nodes, job, "eval-two-groups")
    assert REGISTRY.get(COLUMNAR) == before + 2
    assert len(h.plans[0].alloc_blocks) == 2

    (used_a, blocks_a, _), (used_b, blocks_b, index) = gathers
    assert not blocks_a and not used_a.any()
    (first,) = blocks_b
    want = np.zeros_like(used_b)
    for nid, c in zip(first.node_ids, first.counts):
        want[index[nid]] += first.allocated_vec[: want.shape[1]] * int(c)
    assert want.any() and np.array_equal(used_b, want)

    (name_a, ptg_a, pjob_a), (name_b, ptg_b, pjob_b) = counted
    assert (name_a, name_b) == ("web", "api")
    assert not ptg_a.any() and not pjob_a.any()
    assert not ptg_b.any() and pjob_b.sum() == 300
    assert np.array_equal(np.flatnonzero(pjob_b),
                          np.sort([index[n] for n in first.node_ids]))

    # distinct_hosts at the job's level: no node holds two of the job
    allocs = h.store.snapshot().allocs_by_job(job.id)
    assert len(allocs) == 600
    assert len({a.node_id for a in allocs}) == 600


def test_proposed_allocs_and_touched_nodes_count_a_block_once():
    """A node the plan both stops an allocation on and places a block
    row on: summed once, from proposed_allocs; its block row is not
    added a second time."""
    h = Harness()
    nodes = _racked_nodes(8)
    h.store.upsert_nodes(nodes)
    job = _spread_job(4)
    h.store.upsert_job(job)
    old = mock.alloc(job, nodes[0])
    h.store.upsert_allocs([old])
    vec = old.allocated_vec
    block = AllocBlock(
        id="blk", eval_id="ev", job_id=job.id, job=job, task_group="web",
        name_indices=np.arange(3, dtype=np.int64),
        node_ids=[nodes[0].id, nodes[1].id],
        node_names=[nodes[0].name, nodes[1].name],
        counts=np.array([2, 1], dtype=np.int64), allocated_vec=vec * 2)
    plan = Plan(eval_id="ev")
    plan.append_stopped_alloc(old, "stopped")
    plan.append_block(block)
    ctx = EvalContext(h.store.snapshot(), plan=plan, eval_id="ev")
    assert [a.id for a in ctx.proposed_allocs(nodes[0].id)] == [
        "blk.0", "blk.1"]
    assert [a.id for a in ctx.proposed_allocs(nodes[1].id)] == ["blk.2"]
    assert ctx.proposed_allocs(nodes[2].id) == []
    INFLIGHT._entries.clear()
    cluster = ClusterTensors.build(ctx, nodes)
    i0, i1 = (cluster.node_index[n.id] for n in nodes[:2])
    assert np.array_equal(cluster.used[i0], vec * 4)
    assert np.array_equal(cluster.used[i1], vec * 2)
    assert np.count_nonzero(cluster.used.any(axis=1)) == 2
    ptg, pjob = cluster.placement_counts(job, job.task_groups[0], ctx)
    assert ptg[i0] == pjob[i0] == 2 and ptg[i1] == pjob[i1] == 1
    assert ptg.sum() == 3
    # a rejected node row is in no count
    plan.alloc_blocks[0] = block.without_nodes([nodes[1].id])
    cluster.refresh_usage(ctx)
    assert not cluster.used[i1].any()
    assert cluster.placement_counts(
        job, job.task_groups[0], ctx)[1].sum() == 2


# -- (c) a partly rejected block ---------------------------------------


class _RejectsRows(Harness):
    """Commits the first plan's block without its first `n` node rows
    (the applier's partial commit) and hands back a fresh state."""

    def __init__(self, allocs_rejected: int):
        super().__init__()
        self.allocs_rejected, self.rejected = allocs_rejected, None

    def submit_plan(self, plan):
        if self.rejected is not None:
            return super().submit_plan(plan)
        with self._lock:
            self.plans.append(plan)
            (block,) = plan.alloc_blocks
            rows = int(np.searchsorted(np.cumsum(block.counts),
                                       self.allocs_rejected)) + 1
            bad = block.node_ids[:rows]
            sliced = block.without_nodes(bad)
            self.rejected = block.size - sliced.live_size()
            index = self.store.upsert_plan_results(
                [], alloc_blocks=[sliced])
            result = PlanResult(alloc_blocks=[sliced], alloc_index=index,
                                refresh_index=index,
                                rejected_nodes=sorted(bad))
            self._run_hooks(plan, result)
            return result, self.store.snapshot()


@pytest.mark.parametrize("rejected,again_columnar", [
    (40, False),     # remainder under BULK_PLACE_MIN: the row loop
    (270, True),     # remainder of 256 or more: a second block
])
def test_retry_after_a_partly_rejected_block_places_the_remainder(
        rejected, again_columnar):
    nodes, job = _racked_nodes(400), _spread_job(300)
    before = REGISTRY.get(COLUMNAR)
    h = _drain(nodes, job, "eval-retry", harness=_RejectsRows(rejected))
    assert rejected <= h.rejected < rejected + 3
    first, second = h.plans
    retried = (sum(b.size for b in second.alloc_blocks)
               + sum(map(len, second.node_allocation.values())))
    assert retried == h.rejected
    assert bool(second.alloc_blocks) == again_columnar
    assert REGISTRY.get(COLUMNAR) == before + 1 + int(again_columnar)
    live = [a for a in h.store.snapshot().allocs_by_job(job.id)
            if not a.terminal_status()]
    assert len(live) == job.task_groups[0].count == 300
    assert sorted(a.index() for a in live) == list(range(300))
    assert len({a.id for a in live}) == 300
    ev = h.evals[-1]
    assert ev.status == enums.EVAL_STATUS_COMPLETE
    assert not ev.failed_tg_allocs


# -- (d) the block's new columns survive every copy ----------------------


def _scored_block():
    h = _drain(_racked_nodes(64, cpu=8000), _spread_job(300),
               "eval-scored")
    (block,) = h.plans[0].alloc_blocks
    return h, block


def _same_block(a: AllocBlock, b: AllocBlock) -> None:
    assert b.scores.dtype == a.scores.dtype
    assert np.array_equal(b.scores, a.scores)
    assert (b.nodes_evaluated, b.nodes_in_pool) == (
        a.nodes_evaluated, a.nodes_in_pool) == (64, 64)
    assert np.array_equal(b.name_indices, a.name_indices)
    assert b.node_ids == a.node_ids
    assert np.array_equal(b.counts, a.counts)
    for p in (0, 150, 299):
        assert b.alloc_at(p).metrics == a.alloc_at(p).metrics
        assert b.alloc_at(p).name == a.alloc_at(p).name


@pytest.mark.parametrize("how", ["wire", "deepcopy", "without_nodes",
                                 "with_dropped"])
def test_scores_ride_every_copy_of_a_block(how):
    _, block = _scored_block()
    if how == "wire":
        import json

        copied = wire_decode(json.loads(json.dumps(wire_encode(block))))
    elif how == "deepcopy":
        copied = copy.deepcopy(block)
        assert copied.scores is not block.scores
    elif how == "without_nodes":
        copied = block.without_nodes([block.node_ids[0]])
        assert copied.live_size() == 300 - int(block.counts[0])
    else:
        copied = block.with_dropped([3])
        assert copied.live_size() == 299
    _same_block(block, copied)


def test_a_count_solves_block_keeps_its_shared_metric():
    block = AllocBlock(id="b", job_id="j", task_group="g",
                       name_indices=np.arange(2), node_ids=["n"],
                       node_names=["n"], counts=np.array([2]),
                       mean_score=0.25)
    assert len(block.scores) == 0
    m = block.alloc_at(1).metrics
    assert m.scores == {"bulk.normalized-score": 0.25}
    assert block.alloc_at(0).metrics is m
    assert len(copy.deepcopy(block).scores) == 0
    assert len(wire_decode(wire_encode(block)).scores) == 0


def test_a_scored_block_passes_the_log_into_a_followers_store(tmp_path):
    from nomad_tpu.raft.cluster import RaftCluster

    h, block = _scored_block()
    job = h.store.snapshot().job_by_id(block.job_id)
    want = {a.name: (a.node_id, a.metrics.scores)
            for a in block.iter_allocs()}
    with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
        leader = cluster.wait_for_leader(15.0)
        leader.store.upsert_job(job)
        leader.store.upsert_plan_results(
            [], alloc_blocks=[copy.deepcopy(block)], job=job)
        raft_index = leader.raft.last_applied
        entry = leader.raft.log.get(raft_index)
        assert '"scores"' in entry.wire
        for f in cluster.followers():
            f.raft.wait_applied(raft_index, timeout=10.0)
            snap = f.local_store.snapshot()
            (theirs,) = snap.alloc_blocks()
            _same_block(block, theirs)
            got = {a.name: (a.node_id, a.metrics.scores)
                   for a in snap.allocs_by_job(job.id)}
            assert got == want


# -- (e) what keeps the row loop ----------------------------------------


class _Commit:
    """A commit callback that takes blocks and records what it got."""

    def __init__(self):
        self.rows, self.blocks, self.failed = [], [], 0

    def __call__(self, req, option):
        self.rows.append((req, option))

    def commit_block(self, *args, **kwargs):
        self.blocks.append((args, kwargs))

    def fail_bulk(self, tg, n):
        self.failed += n


def _place(requests, nodes, job, commit, *, preemption=False):
    h = Harness()
    h.store.upsert_nodes(nodes)
    h.store.upsert_job(job)
    snap = h.store.snapshot()
    ctx = EvalContext(snap, plan=Plan(eval_id="ev"), eval_id="ev")
    INFLIGHT._entries.clear()
    TPUPlacer().place(
        ctx, job, requests,
        snap.ready_nodes_in_pool(job.datacenters, job.node_pool), commit,
        preemption_enabled=preemption)
    return ctx


@pytest.mark.parametrize("shape", ["dynamic_port", "canary",
                                   "previous_alloc", "group_of_255",
                                   "request_list", "at_the_host_cutover"])
def test_other_groups_keep_the_row_loop(shape):
    nodes = _racked_nodes(64, cpu=16000, mem=65536)
    job = _spread_job(300, ports=1 if shape == "dynamic_port" else 0)
    tg = job.task_groups[0]
    names = [alloc_name(job.id, tg.name, i) for i in range(300)]
    if shape == "dynamic_port":
        requests = [BulkPlacementRequest(
            task_group=tg, job_id=job.id, name_indices=np.arange(300))]
        k = 300
    elif shape == "at_the_host_cutover":
        k = TPUPlacer.HOST_CUTOVER
        requests = [BulkPlacementRequest(
            task_group=tg, job_id=job.id, name_indices=np.arange(k))]
    else:
        k = 255 if shape == "group_of_255" else 300
        requests = [PlacementRequest(name=n, task_group=tg)
                    for n in names[:k]]
        if shape == "canary":
            requests[7].canary = True
        elif shape == "previous_alloc":
            requests[7].previous_alloc = mock.alloc(job, nodes[0])
            requests[7].ignore_node = nodes[0].id
    before, staged = REGISTRY.get(COLUMNAR), REGISTRY.get(STAGED)
    commit = _Commit()
    _place(requests, nodes, job, commit)
    assert REGISTRY.get(COLUMNAR) == before
    assert REGISTRY.get(STAGED) == staged + (
        0 if shape == "at_the_host_cutover" else 1)
    assert not commit.blocks and len(commit.rows) == k
    assert all(option is not None for _, option in commit.rows)
    assert [req.name for req, _ in commit.rows] == names[:k]
    if shape == "dynamic_port":
        assert all(len(o.allocated_ports) == 1 for _, o in commit.rows)


def test_a_commit_that_takes_no_block_gets_rows():
    nodes, job = _racked_nodes(64, cpu=16000, mem=65536), _spread_job(300)
    rows = []
    before = REGISTRY.get(COLUMNAR)
    _place([BulkPlacementRequest(task_group=job.task_groups[0],
                                 job_id=job.id,
                                 name_indices=np.arange(300))],
           nodes, job, lambda req, option: rows.append(req.name))
    assert REGISTRY.get(COLUMNAR) == before
    assert len(rows) == 300


def test_a_drain_through_the_scheduler_with_a_port_keeps_rows():
    nodes = _racked_nodes(64, cpu=16000, mem=65536)
    before = REGISTRY.get(COLUMNAR)
    h = _drain(nodes, _spread_job(300, ports=1), "eval-port")
    assert REGISTRY.get(COLUMNAR) == before
    (plan,) = h.plans
    assert not plan.alloc_blocks
    assert sum(map(len, plan.node_allocation.values())) == 300


# -- (f) the unplaced tail -------------------------------------------------


def test_the_unplaced_tail_is_one_coalesced_failure(monkeypatch):
    # 40 nodes x 2 fit 80 of 300
    nodes = _racked_nodes(40, cpu=1100, mem=8192)
    job = _spread_job(300)
    job.task_groups[0].tasks[0].resources.cpu = 500
    block_h = _drain(nodes, job, "eval-tail")
    monkeypatch.setattr(TPUPlacer, "_wants_exact_ids",
                        staticmethod(lambda ctx, tg: True))
    row_h = _drain(nodes, job, "eval-tail")
    (block,) = block_h.plans[0].alloc_blocks
    assert block.size == 80 and len(block.scores) == 80
    for h in (block_h, row_h):
        ev = h.evals[-1]
        assert ev.status == enums.EVAL_STATUS_COMPLETE
        assert ev.queued_allocations == {"web": 80}
        (failed,) = ev.failed_tg_allocs.values()
        assert failed.coalesced_failures == 219
        assert failed.nodes_exhausted == 1
        assert failed.dimension_exhausted == {"resources": 1}
        assert failed.nodes_evaluated == failed.nodes_in_pool == 40
        assert failed.scores == {}
        assert len(h.created_evals) == 1      # the blocked evaluation
    assert (_by_name(block_h, job).keys() == _by_name(row_h, job).keys())


def test_with_preemption_only_the_remainder_is_expanded(monkeypatch):
    nodes = _racked_nodes(40, cpu=1100, mem=8192)
    job = _spread_job(300)
    tg = job.task_groups[0]
    tg.tasks[0].resources.cpu = 500
    batches = []

    def preempt_batch(self, ctx, job, tg, reqs, cluster, tgt, commit,
                      **kwargs):
        batches.append((list(reqs), len(commit.blocks), kwargs))
        for req in reqs:
            commit(req, None)

    monkeypatch.setattr(TPUPlacer, "_preempt_batch", preempt_batch)
    commit = _Commit()
    _place([BulkPlacementRequest(task_group=tg, job_id=job.id,
                                 name_indices=np.arange(300))],
           nodes, job, commit, preemption=True)
    ((args, kwargs),) = commit.blocks
    placed = args[4]
    assert len(placed) == 80 and len(kwargs["scores"]) == 80
    ((reqs, blocks_before, kw),) = batches
    # the block was committed first; the remainder alone was expanded
    assert blocks_before == 1
    assert len(reqs) == 220 and kw["n_feasible"] == 40
    assert all(isinstance(r, PlacementRequest) for r in reqs)
    assert sorted([int(r.name[r.name.rfind("[") + 1:-1]) for r in reqs]
                  + placed.tolist()) == list(range(300))
    assert commit.failed == 0 and len(commit.rows) == 220


# -- the applier's overlay of plans in flight ---------------------------


def test_a_block_that_landed_is_not_counted_again_by_the_overlay():
    """The applier lists the results still in flight, then takes its
    snapshot: a commit that lands between the two is in both. A row
    nets itself out by its id; a block was added on top of itself, its
    nodes read twice as full, and the next plan's rows on them were
    rejected (on the chip: 240 of a job's 300, in later rounds)."""
    from nomad_tpu.core.plan_apply import (PlanApplier, PlanQueue,
                                           _OverlaySnapshot)
    from nomad_tpu.state import StateStore

    store = StateStore()
    nodes = _racked_nodes(4, cpu=14000, mem=32000)
    store.upsert_nodes(nodes)
    job = _spread_job(4)
    store.upsert_job(job)
    vec = mock.alloc(job, nodes[0]).allocated_vec * 0
    vec[0], vec[1] = 6000.0, 6000.0

    def block(bid, first):
        return AllocBlock(
            id=bid, eval_id="ev", job_id=job.id, job=job,
            task_group="web", name_indices=np.arange(first, first + 2),
            node_ids=[nodes[0].id, nodes[1].id],
            node_names=[nodes[0].name, nodes[1].name],
            counts=np.array([1, 1], dtype=np.int64), allocated_vec=vec)

    landed, second = block("blk-a", 0), block("blk-b", 2)
    in_flight = PlanResult(alloc_blocks=[landed])
    before = store.snapshot()
    overlay = _OverlaySnapshot(before, [in_flight])
    cols = overlay.node_columns()
    (flying,), rows_too = overlay.inflight(cols)
    assert rows_too is None
    assert flying.rows.tolist() == cols.rows([nodes[0].id,
                                              nodes[1].id]).tolist()
    assert np.array_equal(flying.usage(), np.tile(vec, (2, 1)))
    assert len(overlay.allocs_by_node(nodes[0].id)) == 1

    store.upsert_plan_results([], alloc_blocks=[landed])
    after = store.snapshot()
    overlay = _OverlaySnapshot(after, [in_flight])
    cols = overlay.node_columns()
    assert overlay.inflight(cols) == ([], None)
    assert np.array_equal(cols.read(cols.rows([nodes[0].id]))[0][0], vec)
    assert np.array_equal(after.node_usage(nodes[0].id), vec)
    assert [a.id for a in overlay.allocs_by_node(nodes[0].id)] == ["blk-a.0"]

    # two of the task fit a node: the second plan's rows hold
    plan = Plan(eval_id="ev2", snapshot_index=store.latest_index)
    plan.append_block(second)
    applier = PlanApplier(store, PlanQueue())
    result, rejected = applier._verify(plan, [in_flight])
    assert rejected == []
    assert result.alloc_blocks[0].live_size() == 2
