"""The store's dense per-node columns: beside `_usage_mat` and on its row
index, the capacity each node opens to a new placement (`_avail_mat`:
`available_vec()` while the node is ready and not draining, -inf
otherwise, so availability and the ready mask are one column), kept by
the node writers; and `NodeColumns` (`StateSnapshot.node_columns()`),
which reads both for a list of rows at ONE committed generation without
taking `_write_lock`.

The reader's choice, written down here with its test: a generation check
before and after the gathers (`NodeColumns.read`) and a read a node from
the MVCC rows when the check fails, not a hold of the lock: a commit
keeps `_write_lock` through its listener pass, and the plan applier's
verify would queue behind every round.
"""

import copy
import threading

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.raft.fsm import FSM
from nomad_tpu.state import StateStore
from nomad_tpu.structs import DrainStrategy, enums
from nomad_tpu.structs.alloc import AllocBlock
from nomad_tpu.structs.resources import RESOURCE_DIMS


def _node(cpu=4000, mem=8192):
    n = mock.node()
    n.resources.cpu = cpu
    n.resources.memory_mb = mem
    n.compute_class()
    return n


def _columns(store, node_id):
    """(avail row, ready bit) of one node, straight from the columns."""
    avail = store._avail_mat[store._usage_rows[node_id]].copy()
    return avail, not np.isneginf(avail).any()


def _read(snap, node_ids, per_node=False):
    """(used, avail, ready) of the ids at the snapshot's generation;
    `per_node` refuses the dense read: the MVCC rows alone."""
    cols = snap.node_columns()
    rows = cols.rows(node_ids)
    used, avail = cols._read_per_node(rows) if per_node else cols.read(rows)
    return used, avail, ~np.isneginf(avail).any(axis=1)


def _reads_densely(snap, node_ids):
    cols = snap.node_columns()
    seen = []
    cols_type = type(cols)
    per_node = cols_type._read_per_node
    cols_type._read_per_node = lambda self, rows: (
        seen.append(1), per_node(self, rows))[1]
    try:
        cols.read(cols.rows(node_ids))
    finally:
        cols_type._read_per_node = per_node
    return not seen


def _assert_columns_match_rows(store):
    """Every node of the newest generation: its column is its row's
    open capacity, and the dense read is the per-node read."""
    snap = store.snapshot()
    ids = [n.id for n in snap.nodes()]
    for n in snap.nodes():
        avail, ready = _columns(store, n.id)
        assert ready == (n.status == enums.NODE_STATUS_READY and not n.drain)
        assert np.array_equal(
            avail, n.available_vec() if ready
            else np.full(RESOURCE_DIMS, -np.inf))
    assert _reads_densely(snap, ids)
    for got, want in zip(_read(snap, ids), _read(snap, ids, per_node=True)):
        assert np.array_equal(got, want)
    # the spare last row: a node the store never saw
    assert np.isneginf(store._avail_mat[-1]).all()
    assert not store._usage_mat[-1].any()


# -- the node writers keep the columns ------------------------------------


def _register(store, nodes):
    store.upsert_node(nodes[0])
    return nodes[0].id, True


def _register_batch(store, nodes):
    store.upsert_nodes(nodes)
    return nodes[1].id, True


def _status_down(store, nodes):
    store.upsert_nodes(nodes)
    store.update_node_status(nodes[0].id, enums.NODE_STATUS_DOWN, ts=1.0)
    return nodes[0].id, False


def _status_down_then_ready(store, nodes):
    store.upsert_nodes(nodes)
    store.update_nodes_status([nodes[0].id, nodes[1].id, "no-such-node"],
                              enums.NODE_STATUS_DOWN, ts=1.0)
    assert not _columns(store, nodes[1].id)[1]
    store.update_nodes_status([nodes[0].id], enums.NODE_STATUS_READY, ts=2.0)
    return nodes[0].id, True


def _drain_on(store, nodes):
    store.upsert_nodes(nodes)
    store.update_node_drain(nodes[0].id, DrainStrategy())
    return nodes[0].id, False


def _drain_on_then_off(store, nodes):
    store.upsert_nodes(nodes)
    store.update_node_drain(nodes[0].id, DrainStrategy())
    store.update_node_drain(nodes[0].id, None, mark_eligible=True)
    return nodes[0].id, True


def _ineligible(store, nodes):
    # the gate is _node_plan_valid's (status and drain), not ready():
    # eligibility keeps the scheduler away, the applier does not ask
    store.upsert_nodes(nodes)
    store.update_node_eligibility(nodes[0].id, enums.NODE_SCHED_INELIGIBLE)
    return nodes[0].id, True


def _resized(store, nodes):
    store.upsert_nodes(nodes)
    again = copy.deepcopy(nodes[0])
    again.resources.cpu = 9000
    store.upsert_node(again)
    assert _columns(store, again.id)[0][0] == 9000 - again.reserved.cpu
    return again.id, True


WRITERS = [_register, _register_batch, _status_down,
           _status_down_then_ready, _drain_on, _drain_on_then_off,
           _ineligible, _resized]


@pytest.mark.parametrize("write", WRITERS, ids=lambda f: f.__name__[1:])
def test_columns_follow_the_node_writers(write):
    store = StateStore()
    nodes = [_node(cpu=4000 + 1000 * i) for i in range(3)]
    node_id, ready = write(store, nodes)
    assert _columns(store, node_id)[1] is ready
    _assert_columns_match_rows(store)


def test_deregister_clears_the_row_and_a_new_register_fills_it_again():
    store = StateStore()
    nodes = [_node() for _ in range(3)]
    store.upsert_nodes(nodes)
    store.delete_node(nodes[1].id)
    avail, ready = _columns(store, nodes[1].id)
    assert not ready and np.isneginf(avail).all()
    _assert_columns_match_rows(store)
    ids = [nodes[0].id, nodes[1].id, "never-seen"]
    assert store.snapshot().node_columns().rows(ids)[2] == -1
    used, avail, ready = _read(store.snapshot(), ids)
    assert ready.tolist() == [True, False, False]
    assert np.array_equal(avail[0], nodes[0].available_vec())
    assert np.isneginf(avail[1:]).all() and not used.any()
    # the per-node read says the same of a gone and of an unknown node
    for got, want in zip((used, avail, ready),
                         _read(store.snapshot(), ids, per_node=True)):
        assert np.array_equal(got, want)
    store.upsert_node(nodes[1])
    assert _columns(store, nodes[1].id)[1]
    _assert_columns_match_rows(store)


def test_columns_grow_together_and_keep_their_last_row_spare():
    """Growth swaps the columns for larger ones: the node whose row
    forced it lands in the new ones (rows 255 and 511 were the spare
    rows of the old), and the last row stays nobody's."""
    store = StateStore()
    nodes = [_node(cpu=1000 + i) for i in range(600)]
    store.upsert_nodes(nodes[:255])
    assert store._usage_mat.shape[0] == 256
    store.upsert_node(nodes[255])
    assert store._usage_mat.shape[0] == store._avail_mat.shape[0] == 512
    store.upsert_nodes(nodes[256:])
    assert store._usage_mat.shape[0] == store._avail_mat.shape[0] == 1024
    for i in (254, 255, 256, 510, 511, 512, 599):
        assert store._usage_rows[nodes[i].id] == i
        assert _columns(store, nodes[i].id)[0][0] == 1000 + i
    _assert_columns_match_rows(store)
    # usage written at a growth, too
    job = mock.job()
    store.upsert_job(job)
    store.upsert_allocs([mock.alloc(job, nodes[511], index=0)], ts=1.0)
    assert store._usage_mat[511].any()
    _assert_columns_match_rows(store)


def _populated():
    store = StateStore()
    nodes = [_node(cpu=4000 + 500 * i) for i in range(6)]
    store.upsert_nodes(nodes)
    store.update_node_status(nodes[1].id, enums.NODE_STATUS_DOWN, ts=1.0)
    store.update_node_drain(nodes[2].id, DrainStrategy())
    store.delete_node(nodes[3].id)
    job = mock.job()
    store.upsert_job(job)
    store.upsert_allocs([mock.alloc(job, nodes[0], index=0),
                         mock.alloc(job, nodes[4], index=1)], ts=2.0)
    return store, nodes


def test_restore_dump_rebuilds_the_columns():
    store, nodes = _populated()
    restored = StateStore()
    restored.upsert_node(_node())  # a row the dump does not hold
    restored.restore_dump(store.dump())
    _assert_columns_match_rows(restored)
    ids = [n.id for n in nodes]
    for got, want in zip(_read(restored.snapshot(), ids),
                         _read(store.snapshot(), ids)):
        assert np.array_equal(got, want)
    # and a restore over a live store gives the rows out anew: a reader
    # from before it keeps its own assignment and its own generation
    before = store.snapshot()
    cols = before.node_columns()
    rows, want = cols.rows(ids), _read(before, ids)
    store.upsert_allocs([mock.alloc(mock.job(), nodes[5], index=7)], ts=3.0)
    store.restore_dump(store.dump())
    _assert_columns_match_rows(store)
    assert store._usage_rows is not cols.assignment
    used, avail = cols.read(rows)
    assert np.array_equal(used, want[0]) and np.array_equal(avail, want[1])


def test_a_followers_fsm_replay_keeps_the_columns():
    """The followers of grid-10k-r3 verify nothing, they only keep the
    columns current as they apply the log (numpy alone)."""
    nodes = [_node(cpu=4000 + 500 * i) for i in range(4)]
    job = mock.job()
    log = [
        ("upsert_nodes", (nodes,), {}),
        ("update_node_status", (nodes[0].id, enums.NODE_STATUS_DOWN),
         {"ts": 1.0}),
        ("update_node_drain", (nodes[1].id, DrainStrategy()), {}),
        ("upsert_job", (job,), {}),
        ("upsert_allocs", ([mock.alloc(job, nodes[2], index=0)],),
         {"ts": 2.0}),
        ("update_nodes_status", ([nodes[0].id], enums.NODE_STATUS_READY),
         {"ts": 3.0}),
        ("delete_node", (nodes[3].id,), {}),
    ]
    leader, follower = FSM(StateStore()), FSM(StateStore())
    for command in log:
        leader.apply(command)
    for command in log:
        follower.apply(command)
        _assert_columns_match_rows(follower.store)
    ids = [n.id for n in nodes]
    for got, want in zip(_read(follower.store.snapshot(), ids),
                         _read(leader.store.snapshot(), ids)):
        assert np.array_equal(got, want)


# -- one committed generation, never a mix --------------------------------


def _block(job, nodes, bid="blk"):
    vec = np.zeros(RESOURCE_DIMS)
    vec[0], vec[1] = 1000.0, 1000.0
    return AllocBlock(
        id=bid, eval_id="ev", job_id=job.id, job=job, task_group="web",
        name_indices=np.arange(len(nodes)),
        node_ids=[n.id for n in nodes], node_names=[n.name for n in nodes],
        counts=np.ones(len(nodes), dtype=np.int64), allocated_vec=vec)


def test_a_reader_during_an_open_transaction_sees_one_generation():
    """The test holds a writer mid-apply: the block's first node is in
    the dense usage matrix, its second is not. A reader must see the
    generation before the transaction (both nodes empty) or the one
    after (both holding the block), never the matrix as it stands."""
    store = StateStore()
    nodes = [_node() for _ in range(2)]
    store.upsert_nodes(nodes)
    job = mock.job()
    store.upsert_job(job)
    ids = [n.id for n in nodes]
    block = _block(job, nodes)
    before = store.snapshot()

    half_applied, release = threading.Event(), threading.Event()
    usage_add, calls = store._usage_add, []

    def held_usage_add(node_id, delta, gen, live):
        usage_add(node_id, delta, gen, live)
        calls.append(node_id)
        if len(calls) == 1:
            half_applied.set()
            assert release.wait(10.0)

    store._usage_add = held_usage_add
    writer = threading.Thread(
        target=lambda: store.upsert_plan_results([], alloc_blocks=[block],
                                                 ts=1.0))
    writer.start()
    try:
        assert half_applied.wait(10.0)
        rows = store.usage_rows_for(ids)
        # the matrix itself is the mix a reader must never be handed
        assert store._usage_mat[rows[0]].any()
        assert not store._usage_mat[rows[1]].any()
        during = store.snapshot()
        assert during.index == before.index
        for snap in (before, during):
            assert not _reads_densely(snap, ids)
            used, avail, ready = _read(snap, ids)
            assert not used.any() and ready.all()
            assert np.array_equal(avail[0], nodes[0].available_vec())
    finally:
        release.set()
        writer.join(10.0)
        store._usage_add = usage_add
    after = store.snapshot()
    assert after.index > before.index
    assert _reads_densely(after, ids)
    assert np.array_equal(_read(after, ids)[0],
                          np.tile(block.allocated_vec, (2, 1)))
    # the older snapshot still reads its own generation, a node at a time
    assert not _reads_densely(before, ids)
    assert not _read(before, ids)[0].any()


def test_a_transaction_that_begins_during_the_gathers_is_refused():
    """The check after the gathers: a writer that began while the
    reader was between its first check and its last is seen, and the
    reader falls back to its own generation's rows."""
    store = StateStore()
    nodes = [_node() for _ in range(2)]
    store.upsert_nodes(nodes)
    ids = [n.id for n in nodes]
    snap = store.snapshot()
    assert _reads_densely(snap, ids)

    class BeginsOnRead:
        def __init__(self, mat):
            self.mat = mat

        def __getitem__(self, rows):
            store._next_gen += 1  # what _begin does first
            return self.mat[rows]

    usage = store._usage_mat
    store._usage_mat = BeginsOnRead(usage)
    try:
        assert not _reads_densely(snap, ids)
        store._next_gen = store._index
        used, avail, ready = _read(snap, ids)
    finally:
        store._usage_mat = usage
        store._next_gen = store._index
    assert ready.all() and not used.any()
    assert np.array_equal(avail[1], nodes[1].available_vec())


def test_the_caller_owns_what_a_read_returns():
    store = StateStore()
    node = _node()
    store.upsert_node(node)
    cols = store.snapshot().node_columns()
    used, avail = cols.read(cols.rows([node.id]))
    used += 1.0
    avail[:] = 0.0
    _assert_columns_match_rows(store)
    assert not store._usage_mat[store._usage_rows[node.id]].any()
