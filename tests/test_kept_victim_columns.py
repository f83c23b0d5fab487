"""The store's kept per-node allocation columns (state/alloc_columns.py)
read by build_system_victims against the walk of the snapshot's index
entries, node by node: the same victim columns, ids, groups, port
holders and counts, on stores driven through every writer that keeps
the columns; the walk still takes what the columns cannot vouch for (a
node the plan touches, a write after the snapshot, a block row beside a
row of its own block), and the counters say how many nodes each read."""

from __future__ import annotations

import numpy as np
import pytest

from test_system_preempt import (COUPLED, SHAPES, _block_cluster, _run,
                                 _system_job)
from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.state import StateStore
from nomad_tpu.structs import enums
from nomad_tpu.structs.alloc import (BLOCK_SEP, AllocatedPort, AllocBlock,
                                     BlockPorts)
from nomad_tpu.structs.plan import Plan, PreemptedPositions
from nomad_tpu.tensor import cluster as cluster_mod
from nomad_tpu.tensor.cluster import ClusterTensors, build_system_victims


def _walk_only(monkeypatch):
    """Every node through the walk: nothing read from the columns."""
    monkeypatch.setattr(cluster_mod, "_read_kept",
                        lambda snap, cluster, touched, top:
                        cluster_mod._Kept(len(cluster.nodes)))


def _victims(store, snap=None, plan=None, priority=100):
    snap = snap if snap is not None else store.snapshot()
    ctx = EvalContext(snap, plan)
    nodes = snap.ready_nodes_in_pool(["dc1"], "default")
    cluster = ClusterTensors.build(ctx, nodes)
    return build_system_victims(ctx, cluster, priority), cluster


def _ids_of(vt, r: int) -> list:
    cols = np.flatnonzero(vt.valid[r])
    return vt.ids_at(np.full(len(cols), r), cols)


def _groups_of(vt, r: int) -> list:
    cols = np.flatnonzero(vt.valid[r])
    return vt.groups_at(np.full(len(cols), r), cols)


def _same(kept, walked, cluster) -> None:
    assert kept.v_pad == walked.v_pad
    assert kept.n_victims == walked.n_victims
    for name in ("prio", "vec", "valid", "flagged", "mp", "grp", "cnt0",
                 "holds"):
        np.testing.assert_array_equal(getattr(kept, name),
                                      getattr(walked, name), err_msg=name)
    for r in range(len(cluster.nodes)):
        assert _ids_of(kept, r) == _ids_of(walked, r), r
        assert _groups_of(kept, r) == _groups_of(walked, r), r


def _slots(cols) -> list:
    live = np.flatnonzero(cols.node[:cols.top] >= 0)
    return sorted((int(cols.node[s]), str(cols.ref[s]), int(cols.sub[s]),
                   int(cols.count[s]), int(cols.prio[s]),
                   tuple(cols.vec[s]), bool(cols.flag[s]),
                   bool(cols.ports[s]), cols.groups[cols.grp[s]],
                   int(cols.mp[s])) for s in live.tolist())


def _as_rebuilt(store) -> None:
    """The columns the writers kept are the ones a restore builds."""
    from nomad_tpu.state.alloc_columns import AllocColumns

    kept = store.alloc_columns()
    fresh = AllocColumns(store._usage_row, store._usage_rows,
                         kept.vec.shape[1])
    fresh.rebuild((a for _, a in store._allocs.iterate(store.latest_index)),
                  (b for _, b in
                   store._alloc_blocks.iterate(store.latest_index)))
    assert _slots(kept) == _slots(fresh)
    assert not any(kept._gref[g] for g in kept._gfree)
    assert sum(kept._gref) == len(_slots(kept))


def _both(monkeypatch, store, **kw):
    _as_rebuilt(store)
    kept, cluster = _victims(store, **kw)
    with monkeypatch.context() as m:
        _walk_only(m)
        walked, _ = _victims(store, **kw)
    assert walked.kept == 0
    _same(kept, walked, cluster)
    return kept, cluster


def _mixed_store(built="before_the_writes"):
    """Rows at three priorities (one job with a port, one above the
    system job's reach), two blocks a node row of priority 50 (one with
    a port column) and a block of priority 95, on 24 nodes. The columns
    are built before the first write (every write below keeps them), or
    by the first read."""
    rng = np.random.default_rng(7)
    store = StateStore()
    if built == "before_the_writes":
        store.alloc_columns()
    nodes = []
    for i in range(24):
        node = mock.node()
        node.id = node.name = f"node-{i:03d}"
        node.resources.cpu, node.resources.memory_mb = 16000, 32768
        node.compute_class()
        store.upsert_node(node)
        nodes.append(node)
    jobs = {}
    for name, prio, cpu in (("r20", 20, 300), ("r40", 40, 500),
                            ("r95", 95, 400), ("b50", 50, 700),
                            ("p50", 50, 200), ("b95", 95, 100)):
        job = mock.batch_job()
        job.id = job.name = name
        job.priority = prio
        job.task_groups[0].tasks[0].resources.cpu = cpu
        job.task_groups[0].tasks[0].resources.memory_mb = 128
        store.upsert_job(job)
        jobs[name] = job
    allocs = []
    for i, node in enumerate(nodes):
        for name in ("r20", "r40", "r40", "r95"):
            job = jobs[name]
            a = mock.alloc(job, node)
            a.id = f"{int(rng.integers(1 << 30)):08x}-{len(allocs):04d}"
            a.job = job
            a.allocated_vec = job.task_groups[0].combined_resources().vec()
            if name == "r40" and i % 3 == 0:
                a.allocated_ports = [AllocatedPort(label="http",
                                                   value=20000 + i)]
            allocs.append(a)
    store.upsert_allocs(allocs)
    for name, count in (("b50", 2), ("p50", 1), ("b95", 1)):
        job = jobs[name]
        tg = job.task_groups[0]
        k = count * len(nodes)
        ports = (BlockPorts(["http"], np.arange(21000, 21000 + k,
                                                dtype=np.int32)[:, None])
                 if name == "p50" else None)
        store.upsert_plan_results([], alloc_blocks=[AllocBlock(
            id=f"blk-{name}", eval_id=f"ev-{name}", namespace=job.namespace,
            job_id=job.id, job=job, job_version=job.version,
            task_group=tg.name, name_indices=np.arange(k, dtype=np.int64),
            node_ids=[n.id for n in nodes],
            node_names=[n.name for n in nodes],
            counts=np.full(len(nodes), count, dtype=np.int64),
            allocated_vec=tg.combined_resources().vec(), ports=ports)],
            job=job)
    return store, nodes, jobs


def _rows_of(store, job_id):
    return [a for a in store.snapshot().allocs()
            if a.job_id == job_id and not a.id.startswith("blk-")]


def _stop(allocs, status=enums.ALLOC_DESIRED_STOP):
    out = []
    for a in allocs:
        b = a.copy_for_update()
        b.desired_status = status
        out.append(b)
    return out


def _complete(store, allocs):
    done = []
    for a in allocs:
        b = a.copy_for_update()
        b.client_status = enums.ALLOC_CLIENT_COMPLETE
        done.append(b)
    store.update_allocs_from_client(done)


def _drive(store, nodes, jobs, case) -> None:
    snap = store.snapshot()
    if case == "row_evictions":
        victims = [a for a in _rows_of(store, "r20")][:10]
        evicted = _stop(victims, enums.ALLOC_DESIRED_EVICT)
        store.upsert_plan_results([], preempted_allocs=evicted)
    elif case == "position_evictions":
        ids = [f"blk-b50{BLOCK_SEP}{p}" for p in range(0, 30, 3)]
        vec = snap.alloc_by_id(ids[0]).allocated_vec
        store.upsert_plan_results([], preempted_positions=PreemptedPositions(
            alloc_ids=ids, node_ids=[snap.alloc_by_id(a).node_id for a in ids],
            by=["x"] * len(ids), vecs=np.tile(vec, (len(ids), 1)),
            groups=[("default", "b50", "web")] * len(ids)))
    elif case == "promoted":
        # one position of a node row running: a row beside its block's
        # row slot (that node is walked); a whole node row promoted
        one = snap.alloc_by_id(f"blk-b50{BLOCK_SEP}0")
        both = [snap.alloc_by_id(f"blk-b50{BLOCK_SEP}{p}") for p in (4, 5)]
        running = []
        for a in [one] + both:
            b = a.copy_for_update()
            b.client_status = enums.ALLOC_CLIENT_RUNNING
            running.append(b)
        store.update_allocs_from_client(running)
    elif case == "transition":
        a = snap.alloc_by_id(f"blk-p50{BLOCK_SEP}3")
        store.update_alloc_desired_transitions({a.id: a.desired_transition})
    elif case in ("dropped", "gc"):
        stopped = _stop([snap.alloc_by_id(f"blk-b50{BLOCK_SEP}{p}")
                         for p in (6, 7, 9)]
                        + _rows_of(store, "r40")[:8])
        store.upsert_allocs(stopped)
        _complete(store, stopped)
        if case == "gc":
            # rows, and every position of a block: the block goes too
            gone = _stop(_rows_of(store, "r20")[:5] + [
                snap.alloc_by_id(f"blk-b95{BLOCK_SEP}{p}")
                for p in range(len(nodes))])
            store.upsert_allocs(gone)
            _complete(store, gone)
        store.gc_terminal_allocs(store.latest_index + 1)
        if case == "gc":
            assert store.snapshot().alloc_block_by_id("blk-b95") is None
            cols = store._alloc_cols
            assert cols is None or "blk-b95" not in cols._blocks
    elif case == "stops":
        store.upsert_allocs(_stop(_rows_of(store, "r20")[::2]))
        store.upsert_allocs(_stop([snap.alloc_by_id(
            f"blk-b50{BLOCK_SEP}{p}") for p in (10, 11)]))
    elif case == "ties":
        # ids whose first bytes agree, written in reverse id order: their
        # order comes from the ids, not from the slots
        job = jobs["r40"]
        fresh = []
        for node in nodes:
            for tag in ("b", "a"):
                a = mock.alloc(job, node)
                a.id = f"tied-prefix-{node.id}-{tag}"
                a.job = job
                a.allocated_vec = job.task_groups[0].combined_resources().vec()
                fresh.append(a)
        store.upsert_allocs(fresh)
    elif case == "rows_bulk":
        job = jobs["r40"]
        fresh = []
        for node in nodes[:6]:
            a = mock.alloc(job, node)
            a.job = job
            a.allocated_vec = job.task_groups[0].combined_resources().vec()
            fresh.append(a)
        store.upsert_plan_results(fresh, job=job)


CASES = ["rows_and_blocks", "row_evictions", "position_evictions",
         "promoted", "transition", "dropped", "stops", "gc", "rows_bulk",
         "ties"]


@pytest.mark.parametrize("built", ["before_the_writes", "at_the_first_read"])
@pytest.mark.parametrize("case", CASES)
def test_kept_columns_equal_the_walk(monkeypatch, case, built):
    store, nodes, jobs = _mixed_store(built)
    _drive(store, nodes, jobs, case)
    assert (store._alloc_cols is None) == (built == "at_the_first_read")
    kept, cluster = _both(monkeypatch, store)
    n = len(cluster.nodes)
    assert kept.n_victims > 0 and kept.holds.any()
    # only a node with a block row beside a row of its own block walks
    assert kept.kept == (n - 1 if case == "promoted" else n)


def test_a_store_no_system_evaluation_reads_keeps_no_columns(monkeypatch):
    """No upkeep where nothing reads the columns: every writer, GC and a
    restore leave them unbuilt; the first read builds them."""
    store, nodes, jobs = _mixed_store("at_the_first_read")
    for case in ("promoted", "position_evictions", "gc", "rows_bulk"):
        _drive(store, nodes, jobs, case)
    restored = StateStore()
    restored.restore_dump(store.dump())
    assert store._alloc_cols is None and restored._alloc_cols is None
    kept, cluster = _both(monkeypatch, restored)
    assert kept.kept == len(cluster.nodes)


def test_restore_rebuilds_the_columns(monkeypatch):
    store, nodes, jobs = _mixed_store()
    _drive(store, nodes, jobs, "promoted")
    _drive(store, nodes, jobs, "dropped")
    before, _ = _victims(store)
    restored = StateStore()
    restored.restore_dump(store.dump())
    kept, cluster = _both(monkeypatch, restored)
    assert kept.kept == before.kept
    assert _ids_of(kept, 0) == _ids_of(before, 0)
    # a snapshot of the store before the restore is walked
    old = store.snapshot()
    store.restore_dump(store.dump())
    stale, _ = _victims(store, snap=old)
    assert stale.kept == 0


def test_a_write_after_the_snapshot_sends_its_nodes_to_the_walk(monkeypatch):
    """A snapshot older than a later write: the written nodes are walked
    at the snapshot's index (the columns hold the newer state), the rest
    are read from the columns, and the counters say so."""
    from nomad_tpu.tensor.placer import TPUPlacer, preempt_stats

    store, nodes, jobs = _mixed_store()
    snap = store.snapshot()
    later = _stop(_rows_of(store, "r20")[:3])
    store.upsert_allocs(later)
    written = {a.node_id for a in later}
    kept, cluster = _both(monkeypatch, store, snap=snap)
    assert kept.kept == len(cluster.nodes) - len(written)
    job = _system_job()
    store.upsert_job(job)
    ctx = EvalContext(snap, Plan(eval_id="e", priority=job.priority, job=job))
    before = preempt_stats()
    TPUPlacer().place_system(ctx, job, job.task_groups[0], cluster.nodes,
                             cluster.nodes, preemption_enabled=True)
    for hook in ctx.plan.post_apply_hooks:    # close its in-flight entry
        hook(None)
    after = preempt_stats()
    assert after["column_nodes"] - before["column_nodes"] == len(nodes)
    assert (after["kept_column_nodes"] - before["kept_column_nodes"]
            == len(nodes) - len(written))


def test_a_write_during_the_read_sends_its_node_to_the_walk(monkeypatch):
    """A commit that lands between the gathers and the second stamp
    check: its node is walked at the snapshot, never read half new."""
    store, nodes, jobs = _mixed_store()
    snap = store.snapshot()
    victim = _rows_of(store, "r20")[0]
    real = cluster_mod._stamps
    calls = []

    def stamps(cols, rows):
        calls.append(1)
        if len(calls) == 2:
            store.upsert_allocs(_stop([victim]))
        return real(cols, rows)

    monkeypatch.setattr(cluster_mod, "_stamps", stamps)
    kept, cluster = _victims(store, snap=snap)
    monkeypatch.setattr(cluster_mod, "_stamps", real)
    with monkeypatch.context() as m:
        _walk_only(m)
        walked, _ = _victims(store, snap=snap)
    _same(kept, walked, cluster)
    assert kept.kept == len(cluster.nodes) - 1
    assert victim.id in _ids_of(kept, cluster.node_index[victim.node_id])


def test_nodes_the_plan_touches_are_walked(monkeypatch):
    store, nodes, jobs = _mixed_store()
    job = _system_job()
    store.upsert_job(job)
    plan = Plan(eval_id="e", priority=job.priority, job=job)
    snap = store.snapshot()
    victim = _rows_of(store, "r40")[0]
    a = mock.alloc(job, snap.node_by_id(victim.node_id))
    a.job = job
    a.allocated_vec = job.task_groups[0].combined_resources().vec()
    plan.append_alloc(a)
    plan.append_preempted_alloc(victim, a.id)
    kept, cluster = _both(monkeypatch, store, snap=snap, plan=plan)
    assert kept.kept == len(cluster.nodes) - 1
    assert victim.id not in _ids_of(kept, cluster.node_index[victim.node_id])


def test_block_cluster_columns_equal_the_walk(monkeypatch):
    store, _ = _block_cluster()
    kept, cluster = _both(monkeypatch, store)
    assert kept.kept == len(cluster.nodes)
    assert _ids_of(kept, 0)[0].startswith("blk-")


@pytest.mark.parametrize("spread", ["narrow", "wide"])
def test_candidate_order_is_node_priority_id(spread):
    """The slots' order is (node, priority, id), a block's row by its
    prefix, both where (node, priority, key) packs into one number and
    where the priorities are too far apart for it; ids that share their
    first eight bytes are put in order by the ids themselves."""
    from nomad_tpu.state.alloc_columns import sort_keys

    rng = np.random.default_rng(3)
    n, m = 50, 600
    levels = ([20, 40, 50] if spread == "narrow"
              else [-(1 << 60), 20, 1 << 60])
    ci = rng.integers(0, n, m)
    prio = np.array(levels, np.int64)[rng.integers(0, 3, m)]
    heads = ["aaaaaaaa", "aaaaaaab", "b", "blk-0001", "c0ffee00"]
    ref = np.array([heads[int(rng.integers(len(heads)))]
                    + f"-{int(rng.integers(1 << 20)):05x}-{j}"
                    for j in range(m)], dtype=object)
    sub = np.where(rng.random(m) < 0.2, rng.integers(0, 4, m), -1)
    names = [r + BLOCK_SEP if s >= 0 else r for r, s in zip(ref, sub)]
    order = cluster_mod._candidate_order(ci, prio, sort_keys(names), ref,
                                         sub, n)
    want = sorted(range(m), key=lambda j: (ci[j], prio[j], names[j]))
    assert order.tolist() == want


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("case", ["levels", "max_parallel"])
def test_device_plan_is_the_walks(monkeypatch, seed, case):
    """test_system_preempt's device cases: the same plan, victim ids
    and all, whether the columns are kept or walked."""
    shapes = SHAPES + (COUPLED if case == "max_parallel" else [])
    _, plan, placed, victims, _ = _run(seed, shapes,
                                       enums.SCHED_ALG_TPU_BINPACK)
    with monkeypatch.context() as m:
        _walk_only(m)
        _, wplan, wplaced, wvictims, _ = _run(seed, shapes,
                                              enums.SCHED_ALG_TPU_BINPACK)
    assert placed == wplaced and victims == wvictims
    assert list(plan.preempted.alloc_ids) == list(wplan.preempted.alloc_ids)
    assert [b.node_ids for b in plan.alloc_blocks] == \
        [b.node_ids for b in wplan.alloc_blocks]
