"""Differential tests: TPU tensor kernels vs the host oracle.

The host path (scheduler.rank) reproduces reference semantics exactly;
these tests pin the JAX kernels to it over randomized clusters
(SURVEY.md §7 stage 3/4 test oracles).
"""

import copy
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.rank import score_nodes
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Affinity, Constraint, Spread, SpreadTarget, enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.resources import Resources
from nomad_tpu.tensor.cluster import ClusterTensors, build_task_group_tensors
from nomad_tpu.tensor.placer import TPUPlacer
from nomad_tpu.testing import Harness


def _rand_cluster(store, rng, n_nodes=24, n_allocs=40, dcs=("dc1",)):
    nodes = []
    for _ in range(n_nodes):
        n = mock.node(datacenter=rng.choice(list(dcs)))
        n.resources.cpu = rng.choice([2000, 4000, 8000])
        n.resources.memory_mb = rng.choice([4096, 8192, 16384])
        n.compute_class()
        store.upsert_node(n)
        nodes.append(n)
    filler = mock.job()
    filler.task_groups[0].count = n_allocs
    store.upsert_job(filler)
    for i in range(n_allocs):
        node = rng.choice(nodes)
        a = mock.alloc(filler, node, index=i)
        a.allocated_vec = Resources(
            cpu=rng.choice([100, 250, 500]),
            memory_mb=rng.choice([64, 128, 512])).vec()
        store.upsert_allocs([a])
    return nodes


def _kernel_scores(ctx, job, tg, nodes, algorithm=enums.SCHED_ALG_BINPACK):
    import jax.numpy as jnp

    from nomad_tpu.tensor.kernels import NEG, score_nodes_once

    cluster = ClusterTensors.build(ctx, nodes)
    tgt = build_task_group_tensors(ctx, job, tg, cluster, algorithm=algorithm)
    out = score_nodes_once(
        jnp.asarray(cluster.available), jnp.asarray(cluster.used),
        jnp.asarray(tgt.ask), jnp.asarray(tgt.feasible),
        jnp.asarray(tgt.placed_tg), jnp.asarray(tgt.placed_job),
        jnp.asarray(tgt.affinity_boost), jnp.asarray(np.int32(-1)),
        jnp.asarray(tgt.spread_val_id), jnp.asarray(tgt.spread_val_ok),
        jnp.asarray(tgt.spread_counts), jnp.asarray(tgt.spread_desired),
        jnp.asarray(tgt.spread_has_targets), jnp.asarray(tgt.spread_weight),
        jnp.asarray(-1.0), jnp.asarray(tgt.tg_count),
        jnp.asarray(tgt.dh_job), jnp.asarray(tgt.dh_tg),
        jnp.asarray(tgt.spread_alg),
    )
    scores = np.asarray(out)[: len(nodes)]
    return {nodes[i].id: scores[i] for i in range(len(nodes))
            if scores[i] > NEG / 2}


def _host_scores(ctx, job, tg, nodes, algorithm=enums.SCHED_ALG_BINPACK):
    options = score_nodes(ctx, job, tg, nodes, algorithm=algorithm)
    return {o.node.id: o.final_score for o in options}


@pytest.mark.parametrize("seed", range(6))
def test_score_parity_randomized(seed):
    rng = random.Random(seed)
    store = StateStore()
    nodes = _rand_cluster(store, rng)
    job = mock.job()
    job.task_groups[0].tasks[0].resources = Resources(
        cpu=rng.choice([200, 500, 900]), memory_mb=rng.choice([128, 256, 700]))

    snap = store.snapshot()
    host = _host_scores(EvalContext(snap, eval_id="e1"), job,
                        job.task_groups[0], nodes)
    kern = _kernel_scores(EvalContext(snap, eval_id="e1"), job,
                          job.task_groups[0], nodes)
    assert set(host) == set(kern)
    for nid, hscore in host.items():
        assert kern[nid] == pytest.approx(hscore, abs=1e-6), nid


def test_score_parity_with_affinities_and_constraints():
    rng = random.Random(7)
    store = StateStore()
    nodes = _rand_cluster(store, rng, n_nodes=16)
    # give half the nodes a rack attribute (copy-on-write: _rand_cluster
    # already upserted these rows, so they are shared MVCC history)
    for i, n in enumerate(nodes):
        if i % 2 == 0:
            n = copy.copy(n)
            n.attributes = dict(n.attributes, rack=f"r{i % 4}")
            n.compute_class()
            store.upsert_node(n)
            nodes[i] = n
    job = mock.job(
        constraints=[Constraint("${attr.kernel.name}", "linux", "="),
                     Constraint("${attr.rack}", "", enums.CONSTRAINT_IS_SET)],
        affinities=[Affinity("${attr.rack}", "r0", "=", weight=50),
                    Affinity("${attr.rack}", "r2", "=", weight=-30)],
    )
    snap = store.snapshot()
    host = _host_scores(EvalContext(snap, eval_id="e2"), job, job.task_groups[0], nodes)
    kern = _kernel_scores(EvalContext(snap, eval_id="e2"), job, job.task_groups[0], nodes)
    assert host and set(host) == set(kern)
    for nid in host:
        assert kern[nid] == pytest.approx(host[nid], abs=1e-6)


@pytest.mark.parametrize("targets", [
    [],
    [SpreadTarget("d1", 70), SpreadTarget("d2", 30)],
    [SpreadTarget("d1", 50)],
])
def test_score_parity_spread(targets):
    rng = random.Random(11)
    store = StateStore()
    nodes = _rand_cluster(store, rng, n_nodes=12, dcs=("d1", "d2", "d3"))
    job = mock.job(datacenters=["d1", "d2", "d3"])
    job.task_groups[0].spreads = [
        Spread(attribute="${node.datacenter}", weight=60, targets=targets)]
    # seed some existing allocs of THIS job so property sets are non-empty
    for i in range(5):
        a = mock.alloc(job, rng.choice(nodes), index=i)
        store.upsert_allocs([a])
    store.upsert_job(job)

    snap = store.snapshot()
    host = _host_scores(EvalContext(snap, eval_id="e3"), job, job.task_groups[0], nodes)
    kern = _kernel_scores(EvalContext(snap, eval_id="e3"), job, job.task_groups[0], nodes)
    assert host and set(host) == set(kern)
    for nid in host:
        assert kern[nid] == pytest.approx(host[nid], abs=1e-6)


def test_score_parity_even_spread_missing_attribute():
    """Nodes missing the spread attribute take the -1.0 penalty even when
    no allocs exist yet (SpreadScorer.score checks `ok` before the
    property set; regression for the kernel masking order)."""
    store = StateStore()
    nodes = []
    for i in range(8):
        n = mock.node()
        if i % 2 == 0:
            n.attributes["rack"] = f"r{i % 4}"
            n.compute_class()
        store.upsert_node(n)
        nodes.append(n)
    job = mock.job()
    job.task_groups[0].spreads = [Spread(attribute="${attr.rack}", weight=50)]
    store.upsert_job(job)

    snap = store.snapshot()
    host = _host_scores(EvalContext(snap, eval_id="e5"), job, job.task_groups[0], nodes)
    kern = _kernel_scores(EvalContext(snap, eval_id="e5"), job, job.task_groups[0], nodes)
    assert host and set(host) == set(kern)
    for nid in host:
        assert kern[nid] == pytest.approx(host[nid], abs=1e-6)
    # and the rack-less nodes really do score worse
    rackless = [n.id for n in nodes if "rack" not in n.attributes]
    racked = [n.id for n in nodes if "rack" in n.attributes]
    assert max(host[n] for n in rackless) < min(host[n] for n in racked)


def test_score_parity_spread_algorithm():
    rng = random.Random(13)
    store = StateStore()
    nodes = _rand_cluster(store, rng, n_nodes=10)
    job = mock.job()
    snap = store.snapshot()
    host = _host_scores(EvalContext(snap, eval_id="e4"), job, job.task_groups[0],
                        nodes, algorithm=enums.SCHED_ALG_SPREAD)
    kern = _kernel_scores(EvalContext(snap, eval_id="e4"), job, job.task_groups[0],
                          nodes, algorithm=enums.SCHED_ALG_SPREAD)
    assert host and set(host) == set(kern)
    for nid in host:
        assert kern[nid] == pytest.approx(host[nid], abs=1e-6)


# ---------------------------------------------------------------------------
# end-to-end through the scheduler
# ---------------------------------------------------------------------------


def _tpu_config():
    return SchedulerConfiguration(scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)


def test_tpu_placer_places_all():
    h = Harness()
    for _ in range(8):
        h.store.upsert_node(mock.node())
    job = mock.job()
    h.store.upsert_job(job)
    h.process(mock.eval_for(job), sched_config=_tpu_config())

    ev = h.assert_eval_status(enums.EVAL_STATUS_COMPLETE)
    assert not ev.failed_tg_allocs
    allocs = [a for a in h.store.snapshot().allocs()]
    assert len(allocs) == 10
    # no oversubscription
    by_node = {}
    for a in allocs:
        by_node.setdefault(a.node_id, []).append(a)
    for nid, node_allocs in by_node.items():
        node = h.store.snapshot().node_by_id(nid)
        used = sum(a.allocated_vec for a in node_allocs)
        assert (used <= node.available_vec()).all()


def test_tpu_placer_respects_capacity_and_blocks():
    h = Harness()
    n = mock.node()
    n.resources.cpu = 1000
    n.resources.memory_mb = 1000
    n.compute_class()
    h.store.upsert_node(n)
    job = mock.job()  # 10 x 500MHz/256MB -> only 2 fit
    h.store.upsert_job(job)
    h.process(mock.eval_for(job), sched_config=_tpu_config())

    allocs = h.store.snapshot().allocs_by_job(job.id)
    assert len(allocs) == 2
    # failed placements produce a blocked eval
    assert h.created_evals
    assert h.created_evals[-1].status == enums.EVAL_STATUS_BLOCKED


def test_tpu_placer_distinct_hosts():
    h = Harness()
    for _ in range(6):
        h.store.upsert_node(mock.node())
    job = mock.job(constraints=[
        Constraint(operand=enums.CONSTRAINT_DISTINCT_HOSTS)])
    job.task_groups[0].count = 6
    h.store.upsert_job(job)
    h.process(mock.eval_for(job), sched_config=_tpu_config())

    allocs = h.store.snapshot().allocs_by_job(job.id)
    assert len(allocs) == 6
    assert len({a.node_id for a in allocs}) == 6


def test_tpu_beats_or_matches_host_binpack_score():
    """The kernel scores all nodes where the host samples a shuffled
    log2(N) subset (reference stack.go:82-95), so the per-placement
    normalized scores it achieves must be at least as good on average
    (SURVEY §7: assignment must dominate greedy on score parity)."""
    def run(config):
        h = Harness()
        rng = random.Random(42)
        for _ in range(32):
            n = mock.node()
            n.resources.cpu = rng.choice([2000, 4000])
            n.resources.memory_mb = rng.choice([4096, 8192])
            n.compute_class()
            h.store.upsert_node(n)
        job = mock.job()
        job.task_groups[0].count = 20
        h.store.upsert_job(job)
        h.process(mock.eval_for(job), sched_config=config)
        allocs = h.store.snapshot().allocs_by_job(job.id)
        assert len(allocs) == 20
        scores = []
        for a in allocs:
            key = f"{a.node_id}.normalized-score"
            if a.metrics is not None and key in a.metrics.scores:
                scores.append(a.metrics.scores[key])
        assert scores
        return sum(scores) / len(scores)

    tpu_score = run(_tpu_config())
    host_score = run(SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_BINPACK))
    # production solve runs float32 (pack_solve_args); allow its rounding
    assert tpu_score >= host_score - 1e-5


class TestBulkSolve:
    """The count-based bulk path (tensor/placer.py _place_bulk +
    kernels.solve_bulk): engaged for large fresh BestFit groups, must
    place everything the exact per-placement scan would, respect
    capacity, fail the remainder into a blocked eval, and score on par
    with the exact path's trajectory."""

    def _run(self, bulk_min, count=600, n_nodes=64, cpu=100, mem=64):
        from nomad_tpu.tensor.placer import TPUPlacer

        old = TPUPlacer.BULK_MIN
        TPUPlacer.BULK_MIN = bulk_min
        try:
            h = Harness()
            rng = random.Random(7)
            for _ in range(n_nodes):
                n = mock.node()
                n.resources.cpu = rng.choice([2000, 4000, 8000])
                n.resources.memory_mb = rng.choice([4096, 8192])
                n.compute_class()
                h.store.upsert_node(n)
            job = mock.batch_job()
            job.task_groups[0].count = count
            job.task_groups[0].tasks[0].resources.cpu = cpu
            job.task_groups[0].tasks[0].resources.memory_mb = mem
            h.store.upsert_job(job)
            h.process(mock.eval_for(job), sched_config=_tpu_config())
            snap = h.store.snapshot()
            allocs = [a for a in snap.allocs_by_job(job.id)
                      if not a.terminal_status()]
            return h, job, snap, allocs
        finally:
            TPUPlacer.BULK_MIN = old

    def test_bulk_places_all_and_respects_capacity(self):
        h, job, snap, allocs = self._run(bulk_min=256)
        assert len(allocs) == 600
        from nomad_tpu.structs import allocs_fit

        for n in snap.nodes():
            live = [a for a in snap.allocs_by_node(n.id)
                    if not a.terminal_status()]
            fit, dim, _ = allocs_fit(n, live)
            assert fit, (n.id, dim)
        # bulk allocs carry the shared trajectory-mean score
        scored = [a for a in allocs if a.metrics is not None
                  and "bulk.normalized-score" in a.metrics.scores]
        assert scored

    def test_bulk_score_parity_with_exact_scan(self):
        _, _, _, bulk = self._run(bulk_min=256)
        _, _, _, exact = self._run(bulk_min=1 << 30)

        def mean(allocs):
            out = []
            for a in allocs:
                if a.metrics is None:
                    continue
                for key, v in a.metrics.scores.items():
                    if key.endswith("normalized-score"):
                        out.append(v)
                        break
            return sum(out) / len(out)

        assert len(bulk) == len(exact) == 600
        assert mean(bulk) >= mean(exact) - 5e-3

    def test_bulk_overflow_blocks(self):
        """More asks than the cluster fits: bulk places what fits and
        the rest lands in a blocked eval, same as the exact path."""
        h, job, snap, allocs = self._run(bulk_min=256, count=600,
                                         n_nodes=4, cpu=500, mem=256)
        assert 0 < len(allocs) < 600
        ev = h.assert_eval_status(enums.EVAL_STATUS_COMPLETE)
        assert ev.failed_tg_allocs
        assert ev.blocked_eval


class TestBulkSolverService:
    """The batched solver service (tensor/solver.py): the multi-eval
    kernel chained on a device-resident usage carry must produce the
    same fill-to-capacity trajectories as per-eval solve_bulk_fused
    launches with host-carried usage."""

    def _cluster(self, n_nodes=48, seed=3):
        h = Harness()
        rng = random.Random(seed)
        for _ in range(n_nodes):
            n = mock.node()
            n.resources.cpu = rng.choice([2000, 4000, 8000])
            n.resources.memory_mb = rng.choice([4096, 8192])
            n.compute_class()
            h.store.upsert_node(n)
        return h

    def test_multi_chaining_matches_per_eval_launches(self):
        """The G=8 chained launch must equal G=1 launches whose usage
        carry is threaded on the host — the carry/ordering logic is what
        the batch adds, and what this pins down. Fill semantics and
        score parity are covered by the placer-level TestBulkSolve."""
        import numpy as np
        import jax
        from nomad_tpu.tensor import kernels

        n, d = 64, 4
        rng = np.random.default_rng(11)
        avail = (rng.integers(2, 9, size=(n, d)) * 500).astype(np.float32)
        used0 = np.zeros((n, d), dtype=np.float32)
        feas = np.ones(n, dtype=bool)
        aff = np.zeros(n, dtype=np.float32)
        asks = [np.array([100, 64, 0, 0], np.float32),
                np.array([250, 128, 0, 0], np.float32),
                np.array([50, 32, 0, 0], np.float32)]
        ks = [300, 260, 400]
        seeds = [7, 99, 1234]

        # sequential G=1 launches, usage carried on the host
        used = used0.copy()
        seq_counts = []
        for ask, k, seed in zip(asks, ks, seeds):
            _, out = kernels.solve_bulk_multi(
                jax.device_put(used), jax.device_put(avail),
                jax.device_put(feas[None, :]),
                jax.device_put(aff[None, :]),
                ask[None, :], np.array([k], np.int32),
                np.array([1000.0], np.float32),
                np.array([seed], np.uint32),
                np.zeros(64, np.int32), np.zeros((64, d), np.float32), g=1)
            out = np.asarray(out)[0].astype(np.int64)
            seq_counts.append(out)
            used = used + out[:, None].astype(np.float32) * ask[None, :]

        # one chained multi-eval launch (G padded to 8 like the service)
        g_pad = 8
        ask_m = np.zeros((g_pad, d), np.float32)
        k_m = np.zeros(g_pad, np.int32)
        tgc = np.full(g_pad, 1000.0, np.float32)
        seed_m = np.zeros(g_pad, np.uint32)
        for i, (ask, k, seed) in enumerate(zip(asks, ks, seeds)):
            ask_m[i], k_m[i], seed_m[i] = ask, k, seed
        feas_m = np.repeat(feas[None, :], g_pad, axis=0)
        aff_m = np.repeat(aff[None, :], g_pad, axis=0)
        _, counts = kernels.solve_bulk_multi(
            jax.device_put(used0), jax.device_put(avail),
            jax.device_put(feas_m), jax.device_put(aff_m),
            ask_m, k_m, tgc, seed_m,
            np.zeros(64, np.int32), np.zeros((64, d), np.float32), g=g_pad)
        counts = np.asarray(counts)

        for i in range(3):
            assert (counts[i].astype(np.int64) == seq_counts[i]).all(), i
            assert counts[i].sum() == ks[i], i
        # padded rows place nothing
        assert counts[3:].sum() == 0

    def test_service_end_to_end_capacity(self):
        """Concurrent fresh bulk jobs through the real service: every
        alloc placed, no node oversubscribed."""
        from nomad_tpu.structs import allocs_fit
        from nomad_tpu.tensor.placer import TPUPlacer

        old = TPUPlacer.BULK_MIN
        TPUPlacer.BULK_MIN = 64
        try:
            h = self._cluster()
            jobs = []
            for _ in range(4):
                job = mock.batch_job()
                job.task_groups[0].count = 150
                job.task_groups[0].tasks[0].resources.cpu = 100
                job.task_groups[0].tasks[0].resources.memory_mb = 64
                h.store.upsert_job(job)
                jobs.append(job)
            for job in jobs:
                h.process(mock.eval_for(job), sched_config=_tpu_config())
            snap = h.store.snapshot()
            total = sum(len([a for a in snap.allocs_by_job(j.id)
                             if not a.terminal_status()]) for j in jobs)
            assert total == 600
            for node in snap.nodes():
                live = [a for a in snap.allocs_by_node(node.id)
                        if not a.terminal_status()]
                fit, dim, _ = allocs_fit(node, live)
                assert fit, (node.id, dim)
        finally:
            TPUPlacer.BULK_MIN = old


def test_bulk_solve_after_outside_placements_does_not_block():
    """Usage the solver service's carry never saw (placements made
    outside it: the per-placement tier, the host path) must cost one
    rejected plan, not the eval: the rejection resyncs the carry, so the
    retry lands. Before, the retry refilled the same full node until the
    batch eval's two attempts were gone and it blocked for a minute
    (found by chip_smoke.py: a service-job wave, then a bulk job)."""
    from nomad_tpu.core.server import Server, ServerConfig
    from nomad_tpu.tensor.solver import get_service

    srv = Server(ServerConfig(num_workers=1, heartbeat_ttl=3600.0,
                              gc_interval=3600.0, sched_config=_tpu_config()))
    srv.start()
    try:
        nodes = [mock.node() for _ in range(4)]     # 4000 MHz / 8192 MB each
        for n in nodes:
            srv.register_node(n)

        def bulk(cpu, mem, count=256):
            j = mock.batch_job()
            j.task_groups[0].count = count
            j.task_groups[0].tasks[0].resources.cpu = cpu
            j.task_groups[0].tasks[0].resources.memory_mb = mem
            j.task_groups[0].ephemeral_disk.size_mb = 1    # cpu-bound
            return j

        first = bulk(10, 8)             # BestFit: all 256 on one node
        srv.register_job(first)
        assert srv.wait_for_idle(30.0)
        snap = srv.store.snapshot()
        homes = {a.node_id for a in snap.allocs_by_job(first.id)}
        assert len(homes) == 1
        home = next(n for n in snap.nodes() if n.id in homes)
        # fill that node behind the service's back: 2560 + 1400 of 4000
        outside = mock.job()
        outside.task_groups[0].tasks[0].resources.cpu = 1400
        outside.task_groups[0].tasks[0].resources.memory_mb = 64
        srv.store.upsert_job(outside)
        srv.store.upsert_allocs([mock.alloc(outside, home)])

        svc0 = dict(get_service().stats)
        # the stale carry sees 1440 MHz free at home and sends a whole
        # bulk-sized row there; the retry is bulk-sized too, so it goes
        # through the service again
        second = bulk(2, 1, count=700)
        srv.register_job(second)
        assert srv.wait_for_idle(30.0)
        svc = {k: get_service().stats[k] - svc0[k] for k in svc0}
        live = [a for a in srv.store.snapshot().allocs_by_job(second.id)
                if not a.terminal_status()]
        assert len(live) == 700
        assert srv.blocked.blocked_count() == 0
        assert svc["rejections"] >= 1 and svc["resyncs"] >= 1, svc
        from nomad_tpu.structs import allocs_fit

        for n in srv.store.snapshot().nodes():
            fit, dim, _ = allocs_fit(n, [
                a for a in srv.store.snapshot().allocs_by_node(n.id)
                if not a.terminal_status()])
            assert fit, (n.id, dim)
    finally:
        srv.stop()
