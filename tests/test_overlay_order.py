"""A usage gather reads the in-flight overlay before committed usage.

An overlay entry closes right after its plan's commit is published. A
gather that reads committed usage first and the overlay second loses a
commit that lands between the two reads: its placements are in
neither, the solve fills nodes that are already full and the applier
rejects the rows. The faster the device loop, the more commits land
inside other evaluations' gathers (PERF.md section 6, PR 29)."""

import numpy as np

from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.state import StateStore
from nomad_tpu.structs.plan import Plan
from nomad_tpu.structs.resources import Resources
from nomad_tpu.tensor.cluster import ClusterTensors
from nomad_tpu.tensor.overlay import INFLIGHT, InflightOverlay

F32 = np.float32


def _racing_pair():
    store = StateStore()
    nodes = [mock.node() for _ in range(6)]
    for n in nodes:
        store.upsert_node(n)
    INFLIGHT._entries.clear()
    theirs, mine = Plan(eval_id="theirs"), Plan(eval_id="mine")
    ctx = EvalContext(store.snapshot(), plan=mine, eval_id="mine")
    cluster = ClusterTensors.build(ctx, nodes)
    vec = Resources(cpu=500, memory_mb=256).vec() * 3
    row = cluster.node_index[nodes[2].id]
    INFLIGHT.register(cluster, np.array([row]), vec[None, :], theirs)
    return ctx, cluster, theirs, mine, row, vec


def test_a_commit_between_the_two_reads_of_a_gather_is_not_lost(monkeypatch):
    ctx, cluster, theirs, _, row, vec = _racing_pair()
    fold = InflightOverlay.fold

    def commit_lands_then_fold(self, *args, **kwargs):
        # the racing plan's commit and its hook, after this gather has
        # read committed usage and before it folds the overlay
        for hook in theirs.post_apply_hooks:
            hook(None)
        return fold(self, *args, **kwargs)

    monkeypatch.setattr(InflightOverlay, "fold", commit_lands_then_fold)
    buf = np.empty(cluster.available.shape, F32)
    cluster.refresh_usage(ctx, out=buf)
    assert not INFLIGHT._entries                # the entry did close
    assert np.array_equal(buf[row], vec.astype(F32))
    assert not np.delete(buf, row, axis=0).any()


def test_open_entries_are_the_others_live_ones():
    ctx, cluster, theirs, mine, row, vec = _racing_pair()
    INFLIGHT.register(cluster, np.array([0]), vec[None, :], mine)
    assert len(INFLIGHT.open_entries()) == 2
    (entry,) = INFLIGHT.open_entries(exclude_plan=mine)
    assert entry["plan"] == id(theirs) and list(entry["rows"]) == [row]
    # what was read open folds even after it has closed
    for hook in theirs.post_apply_hooks:
        hook(None)
    used = np.zeros(cluster.available.shape)
    INFLIGHT.fold(used, cluster.node_index, entries=[entry])
    assert np.array_equal(used[row], vec)
    # the TTL backstop drops an entry whose plan never came back
    next(iter(INFLIGHT._entries.values()))["born"] -= 3600.0
    assert INFLIGHT.open_entries() == []
    assert INFLIGHT.stats["expired"] >= 1


def test_the_solver_services_resync_reads_the_overlay_first():
    """BulkSolverService._resync_base: a commit that lands while the
    feed's device twin is read is still in the carry (from the overlay,
    read before; here the twin's base stays empty, so exactly once)."""
    import jax.numpy as jnp

    from nomad_tpu.tensor.solver import BulkSolverService

    _, cluster, theirs, _, row, vec = _racing_pair()
    static = cluster.static
    d = cluster.available.shape[1]

    class Req:
        @staticmethod
        def used_dev_fn(mesh):
            for hook in theirs.post_apply_hooks:   # the racing commit
                hook(None)
            return jnp.zeros((static.n_pad, d), jnp.float32)

    out = np.asarray(BulkSolverService()._resync_base(
        Req, static=static, mesh=None, d=d, ledger_entries=[]))
    assert not INFLIGHT._entries
    assert np.array_equal(out[row], vec.astype(F32)[:d])
    assert not np.delete(out, row, axis=0).any()
