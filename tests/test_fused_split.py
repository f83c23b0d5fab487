"""The fused solve's arguments split by who can change them: the usage
matrix travels alone (gathered under _PER_EVAL_SOLVE_LOCK into the f32
buffer the stage allocated), everything else is packed and on the device
before the lock is taken (placer.stage). What the scan computes does
not change: bit for bit the plain solve_task_group on the same f32
inputs."""

import copy
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Constraint, Spread, enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.plan import Plan
from nomad_tpu.structs.resources import (NodeDeviceResource, RequestedDevice,
                                         Resources)
from nomad_tpu.tensor.cluster import (ClusterTensors, _pad_pow2,
                                      build_task_group_tensors)
from nomad_tpu.tensor.overlay import INFLIGHT
from nomad_tpu.tensor.placer import TPUPlacer
from nomad_tpu.testing import Harness

F32 = np.float32


def _tpu_config():
    return SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)


def _seeded_cluster(store, rng, n_nodes=40, n_allocs=60, gpus=0):
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.resources.cpu = rng.choice([4000, 8000, 16000])
        n.resources.memory_mb = rng.choice([8192, 16384])
        n.meta["rack"] = f"r{i % 5}"
        if gpus:
            n.resources.devices = [NodeDeviceResource(
                vendor="nvidia", type="gpu", name="a100",
                instance_ids=[f"a100-{k}" for k in range(gpus)],
                attributes={"memory": "40000"})]
            n.resources.total_cores = 8
        n.compute_class()
        store.upsert_node(n)
        nodes.append(n)
    filler = mock.job()
    filler.task_groups[0].count = n_allocs
    store.upsert_job(filler)
    for i in range(n_allocs):
        a = mock.alloc(filler, rng.choice(nodes), index=i)
        a.allocated_vec = Resources(
            cpu=rng.choice([100, 250, 500]),
            memory_mb=rng.choice([64, 128, 512])).vec()
        store.upsert_allocs([a])
    return nodes


def _shape_spread(job):
    job.task_groups[0].spreads = [Spread(attribute="${meta.rack}", weight=50)]


def _shape_distinct_hosts(job):
    job.constraints.append(Constraint(operand=enums.CONSTRAINT_DISTINCT_HOSTS))


def _shape_distinct_property(job):
    job.constraints.append(Constraint(
        ltarget="${meta.rack}", rtarget="3",
        operand=enums.CONSTRAINT_DISTINCT_PROPERTY))


def _shape_extra_ask(job):
    res = job.task_groups[0].tasks[0].resources
    res.devices = [RequestedDevice(name="nvidia/gpu", count=1)]
    res.cores = 2


SHAPES = {"spread": _shape_spread, "distinct_hosts": _shape_distinct_hosts,
          "distinct_property": _shape_distinct_property,
          "extra_ask": _shape_extra_ask}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("seed", [0, 1])
def test_static_plus_usage_equals_the_plain_scan_bit_for_bit(shape, seed):
    """The staged statics and the usage gathered into the stage's f32
    buffer, through solve_task_group_fused, against solve_task_group on
    the same values cast to f32 by hand."""
    import jax

    from nomad_tpu.tensor.kernels import (solve_task_group,
                                          solve_task_group_fused)

    rng = random.Random(seed)
    store = StateStore()
    nodes = _seeded_cluster(store, rng,
                            gpus=2 if shape == "extra_ask" else 0)
    job = mock.job()
    job.task_groups[0].count = k = 24
    SHAPES[shape](job)
    store.upsert_job(job)
    tg = job.task_groups[0]
    ctx = EvalContext(store.snapshot(), eval_id=f"split-{shape}-{seed}")
    cluster = ClusterTensors.build(ctx, nodes)
    tgt = build_task_group_tensors(ctx, job, tg, cluster,
                                   algorithm=enums.SCHED_ALG_TPU_BINPACK)
    has_extra = tgt.extra_ask is not None and len(tgt.extra_ask) > 0
    assert has_extra == (shape == "extra_ask")
    assert bool(tgt.spread_val_id.shape[0]) == (shape == "spread")
    assert bool(tgt.dh_job) == (shape == "distinct_hosts")
    assert bool(tgt.dp_val_id is not None and len(tgt.dp_val_id)) == (
        shape == "distinct_property")

    k_pad = _pad_pow2(k, floor=1)
    penalty_idx = np.full(k_pad, -1, dtype=np.int32)
    penalty_idx[3] = 5                     # one rescheduled request
    active = np.zeros(k_pad, dtype=bool)
    active[:k] = True
    tie_perm = np.random.default_rng(seed).permutation(
        cluster.n_pad).astype(np.int32)

    statics, usage, extra_used = TPUPlacer()._stage_statics(
        tgt, cluster, penalty_idx, active, tie_perm)
    assert usage.dtype == F32 and usage.shape == cluster.available.shape
    f64 = ClusterTensors.build(ctx, nodes).used
    cluster.refresh_usage(ctx, out=usage)
    assert cluster.used is usage
    assert np.array_equal(usage, np.asarray(f64, F32))
    assert usage[: len(nodes)].any()       # the filler's allocations
    avail, ask = cluster.available, tgt.ask
    if has_extra:
        assert extra_used.dtype == F32
        usage = np.concatenate([usage, extra_used], axis=1)
        avail = np.concatenate([avail, tgt.extra_cap], axis=1)
        ask = np.concatenate([ask, tgt.extra_ask])
    else:
        assert extra_used is None
    # the split moves no byte: what the statics lost the usage carries
    n, d = usage.shape
    assert statics[0].shape == (n, d + 6)
    got = np.asarray(solve_task_group_fused(jax.device_put(usage), *statics))

    p = 0 if tgt.dp_val_id is None else len(tgt.dp_val_id)
    dp = ((tgt.dp_val_id.astype(np.int32), tgt.dp_val_ok.astype(bool),
           tgt.dp_counts.astype(np.int32), tgt.dp_limit.astype(F32))
          if p else (np.zeros((0, n), np.int32), np.zeros((0, n), bool),
                     np.zeros((0, 1), np.int32), np.zeros(0, F32)))
    dev_aff = (np.zeros(n, F32) if tgt.dev_affinity is None
               else tgt.dev_affinity.astype(F32))
    choices, founds, scores = solve_task_group(
        avail.astype(F32), usage, tgt.placed_tg.astype(np.int32),
        tgt.placed_job.astype(np.int32), ask.astype(F32),
        tgt.feasible.astype(bool), tgt.affinity_boost.astype(F32), dev_aff,
        penalty_idx, active,
        tgt.spread_val_id.astype(np.int32), tgt.spread_val_ok.astype(bool),
        tgt.spread_counts.astype(np.int32), tgt.spread_desired.astype(F32),
        tgt.spread_has_targets.astype(bool), tgt.spread_weight.astype(F32),
        *dp, F32(-1.0), F32(tgt.tg_count), np.bool_(tgt.dh_job),
        np.bool_(tgt.dh_tg), np.bool_(tgt.spread_alg), tie_perm)
    assert np.array_equal(got[0], np.asarray(choices).astype(got.dtype))
    assert np.array_equal(got[1] > 0.5, np.asarray(founds))
    assert np.array_equal(got[2], np.asarray(scores))   # bit for bit
    assert np.asarray(founds)[:k].any()


def _device_solve_job(count=24):
    job = mock.job()
    job.task_groups[0].count = count
    _shape_spread(job)                     # never the count solve
    return job


def test_staged_solves_counts_device_solves_and_no_host_cutover_group():
    h = Harness()
    for i in range(16):
        n = mock.node()
        n.meta["rack"] = f"r{i % 4}"
        n.compute_class()
        h.store.upsert_node(n)
    before = REGISTRY.get("nomad.placer.staged_solves")
    for done in (1, 2):
        job = _device_solve_job()
        h.store.upsert_job(job)
        h.process(mock.eval_for(job), sched_config=_tpu_config())
        assert len(h.store.snapshot().allocs_by_job(job.id)) == 24
        assert REGISTRY.get("nomad.placer.staged_solves") == before + done
    small = _device_solve_job(count=TPUPlacer.HOST_CUTOVER)
    h.store.upsert_job(small)
    cut = REGISTRY.get("nomad.placer.host_cutover_groups")
    h.process(mock.eval_for(small), sched_config=_tpu_config())
    assert REGISTRY.get("nomad.placer.host_cutover_groups") == cut + 1
    assert REGISTRY.get("nomad.placer.staged_solves") == before + 2


def test_second_group_gathers_the_first_groups_in_plan_usage(monkeypatch):
    """Group B's gather under the lock goes into its own staged buffer
    and still carries group A's placements from the plan, once: the
    evaluation's own overlay entry is not folded on top."""
    h = Harness()
    for _ in range(24):
        h.store.upsert_node(mock.node())
    job = mock.job()
    second = copy.deepcopy(job.task_groups[0])
    second.name = "api"
    job.task_groups.append(second)
    for tg in job.task_groups:
        tg.count = 20
        tg.spreads = [Spread(attribute="${node.unique.id}", weight=50)]
    h.store.upsert_job(job)

    gathers = []
    refresh = ClusterTensors.refresh_usage

    def spy(self, ctx, out=None):
        refresh(self, ctx, out=out)
        if out is not None:
            planned = {nid: len(allocs)
                       for nid, allocs in ctx.plan.node_allocation.items()}
            gathers.append((out, self.used is out, out.copy(), planned,
                            dict(self.node_index), ctx.tg_vec(second)))

    monkeypatch.setattr(ClusterTensors, "refresh_usage", spy)
    INFLIGHT._entries.clear()
    h.process(mock.eval_for(job), sched_config=_tpu_config())
    allocs = h.store.snapshot().allocs_by_job(job.id)
    assert len(allocs) == 40

    (buf_a, is_a, used_a, planned_a, _, _), \
        (buf_b, is_b, used_b, planned_b, index, vec) = gathers
    assert is_a and is_b and buf_a is not buf_b
    assert buf_a.dtype == buf_b.dtype == F32
    assert not planned_a and not used_a.any()
    assert sum(planned_b.values()) == 20
    want = np.zeros_like(used_b)
    for nid, c in planned_b.items():
        want[index[nid]] = vec[: want.shape[1]] * c
    assert np.array_equal(used_b, want)


def test_a_racing_evaluation_gathers_an_open_overlay_entry():
    store = StateStore()
    nodes = [mock.node() for _ in range(6)]
    for n in nodes:
        store.upsert_node(n)
    INFLIGHT._entries.clear()
    theirs, mine = Plan(eval_id="theirs"), Plan(eval_id="mine")
    vec = Resources(cpu=500, memory_mb=256).vec()
    ctx = EvalContext(store.snapshot(), plan=mine, eval_id="mine")
    cluster = ClusterTensors.build(ctx, nodes)
    row = cluster.node_index[nodes[2].id]
    INFLIGHT.register(cluster, np.array([row]), (vec * 3)[None, :], theirs)
    buf = np.empty(cluster.available.shape, F32)
    cluster.refresh_usage(ctx, out=buf)
    assert cluster.used is buf
    assert np.array_equal(buf[row], (vec * 3).astype(F32))
    assert not np.delete(buf, row, axis=0).any()
    # another row order than the entry's: the node is found by its id,
    # and a node that order does not hold is left out
    fewer = ClusterTensors.build(ctx, nodes[1:])
    assert fewer.node_index is not cluster.node_index
    fewer.refresh_usage(ctx, out=buf)
    assert np.array_equal(buf[fewer.node_index[nodes[2].id]],
                          (vec * 3).astype(F32))
    assert np.count_nonzero(buf.any(axis=1)) == 1
    without = ClusterTensors.build(ctx, nodes[3:])
    without.refresh_usage(ctx, out=buf)
    assert not buf.any()
    # an evaluation does not count its own entries twice
    own = EvalContext(store.snapshot(), plan=theirs, eval_id="theirs")
    ClusterTensors.build(own, nodes).refresh_usage(own, out=buf)
    assert not buf.any()
    # the plan's outcome closes the entry
    for hook in theirs.post_apply_hooks:
        hook(None)
    cluster.refresh_usage(ctx, out=buf)
    assert not buf.any()
