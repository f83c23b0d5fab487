"""What the per-placement scan keeps on the device: the node set's arrays
are uploaded once a ClusterStatic (the same cache entries, keys and
dtypes as the count solve's), and a staged solve of a job the static
has seen ships only what its evaluation fixes."""

import numpy as np

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.obs import TRACER
from nomad_tpu.obs.trace import R_ARGS, R_NAME
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.structs import Spread, enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.tensor.cluster import (ClusterTensors, _pad_pow2,
                                      build_task_group_tensors)
from nomad_tpu.tensor.placer import TPUPlacer
from nomad_tpu.testing import Harness

F32 = np.float32
RACKS = 4


def _tpu_config():
    return SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)


def _racked_node(i):
    n = mock.node()
    n.meta["rack"] = f"r{i % RACKS}"
    n.compute_class()
    return n


def _spread_job(count=24):
    job = mock.job()
    job.task_groups[0].count = count
    job.task_groups[0].spreads = [Spread(attribute="${meta.rack}", weight=50)]
    return job


def _statics(h):
    return dict(getattr(h.store, "_tensor_statics", {}))


class _Staged:
    """Records every staged solve's device arguments."""

    def __init__(self, monkeypatch):
        self.args = []
        solve = TPUPlacer._solve_staged

        def spy(placer, ctx, tg, cluster, k, k_pad, staged):
            self.args.append((cluster.static, staged[0]))
            return solve(placer, ctx, tg, cluster, k, k_pad, staged)

        monkeypatch.setattr(TPUPlacer, "_solve_staged", spy)


def _resident_ids(static):
    return {id(a) for v in static.device_arrays.values()
            for a in (v if isinstance(v, tuple) else (v,))}


def _place(h, job):
    h.store.upsert_job(job)
    h.process(mock.eval_for(job), sched_config=_tpu_config())
    assert len(h.store.snapshot().allocs_by_job(job.id)) == job.task_groups[0].count


def test_a_second_solve_of_a_shape_uploads_no_node_set_array(monkeypatch):
    staged = _Staged(monkeypatch)
    h = Harness()
    h.store.upsert_nodes([_racked_node(i) for i in range(16)])
    TRACER.set_enabled(True)
    TRACER.clear()
    resident = REGISTRY.get("nomad.placer.stage_resident")
    solves = REGISTRY.get("nomad.placer.staged_solves")
    _place(h, _spread_job())
    static, = _statics(h).values()
    uploaded = dict(static.device_arrays)
    # the first solve of the node set puts capacity, mask, affinity,
    # the rack's interned rows, the zero counts and the (empty)
    # distinct_property rows
    assert "avail" in uploaded and "placed0" in uploaded
    assert REGISTRY.get("nomad.placer.staged_solves") == solves + 1
    assert REGISTRY.get("nomad.placer.stage_resident") == resident

    _place(h, _spread_job())
    assert _statics(h) == {k: static for k in _statics(h)}
    assert REGISTRY.get("nomad.placer.staged_solves") == solves + 2
    assert REGISTRY.get("nomad.placer.stage_resident") == resident + 1
    # nothing new on the device, and the second solve read those arrays
    assert static.device_arrays == uploaded
    (_, first), (_, second) = staged.args
    assert all(a is b for a, b in zip(first[:8], second[:8]))
    ids = _resident_ids(static)
    assert all(id(a) in ids for a in second[:8])
    assert id(second[8]) not in ids
    # the span's bytes are the per-evaluation buffer alone
    from nomad_tpu.tensor.kernels import eval_sizes

    stages = [r for r in TRACER.spans() if r[R_NAME] == "placer.stage"]
    assert len(stages) == 2
    n_pad, k_pad = static.n_pad, _pad_pow2(24, floor=1)
    want = 4 * sum(eval_sizes(n_pad, 4, 0, k_pad, 1, RACKS, 0, 1))
    assert second[8].nbytes == want
    assert [r[R_ARGS]["bytes"] for r in stages] == [want, want]


def test_a_job_with_allocations_ships_its_counts_beside_the_buffer(
        monkeypatch):
    staged = _Staged(monkeypatch)
    h = Harness()
    h.store.upsert_nodes([_racked_node(i) for i in range(16)])
    from nomad_tpu.tensor.kernels import solve_task_group_fused

    job = _spread_job()
    _place(h, job)
    programs = solve_task_group_fused._cache_size()
    job.task_groups[0].count = 48
    resident = REGISTRY.get("nomad.placer.stage_resident")
    _place(h, job)                     # 24 more beside its 24
    (static, _), (same, args) = staged.args
    assert same is static
    # shipped or resident, the counts have one shape: no new program
    assert solve_task_group_fused._cache_size() == programs
    placed = np.asarray(args[3])
    assert id(args[3]) not in _resident_ids(static)
    assert placed.shape == (2, static.n_pad) and placed.sum() == 2 * 24
    # its counts are the evaluation's; every node-set array was resident
    assert REGISTRY.get("nomad.placer.stage_resident") == resident + 1


def test_a_new_node_set_reads_no_array_of_the_old_static(monkeypatch):
    staged = _Staged(monkeypatch)
    h = Harness()
    h.store.upsert_nodes([_racked_node(i) for i in range(16)])
    _place(h, _spread_job())
    old, = _statics(h).values()
    old_ids = _resident_ids(old)
    h.store.upsert_node(_racked_node(16))    # a new node_set_version
    resident = REGISTRY.get("nomad.placer.stage_resident")
    _place(h, _spread_job())
    new, = _statics(h).values()
    assert new is not old and new.version != old.version
    static, args = staged.args[-1]
    assert static is new
    assert not {id(a) for a in args} & old_ids
    assert all(id(a) in _resident_ids(new) for a in args[:8])
    # the first solve of the new set uploaded its arrays anew
    assert REGISTRY.get("nomad.placer.stage_resident") == resident
    assert len(new.nodes) == 17


def test_a_count_solve_reads_the_scans_cache_entries_as_its_own():
    """The scan shares ensure_resident's entries with the count solve:
    after a scan has staged on a static, the count solve finds the
    arrays it would have uploaded (same values, same dtypes) and its
    counts are those of a static the scan never touched."""
    import jax

    from nomad_tpu.tensor.kernels import solve_bulk_multi
    from nomad_tpu.tensor.solver import ensure_resident

    h = Harness()
    h.store.upsert_nodes([_racked_node(i) for i in range(16)])
    _place(h, _spread_job())
    static, = _statics(h).values()
    plain = mock.job()
    plain.task_groups[0].count = 40
    h.store.upsert_job(plain)
    snap = h.store.snapshot()
    nodes = snap.ready_nodes_in_pool(plain.datacenters, plain.node_pool)
    ctx = EvalContext(snap, eval_id="count-after-scan")
    cluster = ClusterTensors.build(ctx, nodes)
    assert cluster.static is static
    tgt = build_task_group_tensors(ctx, plain, plain.task_groups[0], cluster,
                                   algorithm=enums.SCHED_ALG_TPU_BINPACK)
    # the scan's mask and affinity entries are this job's too
    assert tgt.feas_base is not None
    assert ("m", id(tgt.feas_base)) in static.device_arrays
    assert ("a", id(tgt.affinity_boost)) in static.device_arrays
    shared = ensure_resident(static, tgt.feas_base, tgt.affinity_boost)
    fresh = jax.device_put((static.available.astype(F32), tgt.feas_base,
                            tgt.affinity_boost.astype(F32)))
    for a, b in zip(shared, fresh):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))

    used = cluster.used.astype(F32)
    d = used.shape[1]

    def counts(resident):
        avail, feas, aff = resident
        _, out = solve_bulk_multi(
            jax.device_put(used), avail, feas[None, :], aff[None, :],
            tgt.ask.astype(F32)[None, :], np.array([40], np.int32),
            np.array([tgt.tg_count], F32), np.array([7], np.uint32),
            np.zeros(64, np.int32), np.zeros((64, d), F32), g=1)
        return np.asarray(out)[0]

    got = counts(shared)
    assert got.sum() == 40
    assert np.array_equal(got, counts(fresh))
