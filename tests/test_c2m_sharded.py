"""Multi-chip C2M (round 14): the flagship pipeline through the
mesh-sharded engine with solve/apply overlap.

Three properties pinned here:

- **e2e parity across mesh sizes**: the same pinned workload produces
  bit-identical placements (per-job alloc counts, per-node multisets,
  normalized scores) on a fresh solver service at mesh sizes 1, 2, 4
  and 8 — for both the greedy bulk tier and the joint auction tier.
- **warm sharded launches never retrace or host-transfer**: after the
  first launch of a shape, repeating it adds zero compile-cache entries
  (the shape-keyed no_retrace window with explicit NamedSharding
  device_put on every input).
- **double-buffer correctness**: with slow plan-applies racing the
  pipelined service (dispatch i+1 before fetch i), an exactly-filling
  workload still lands every placement with zero oversubscription —
  a launch solved against a stale carry, a resync that dropped the
  unfetched launch, or a lost correction would all break exact fill.
"""

import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.structs import enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.resources import RESOURCE_DIMS
from nomad_tpu.testing import Harness


def _fresh_service(monkeypatch, mesh_devices: int):
    """A private BulkSolverService pinned to `mesh_devices`, installed
    as the process singleton for the duration of the test."""
    from nomad_tpu.tensor import solver as solver_mod

    monkeypatch.setenv("NOMAD_TPU_MESH_DEVICES", str(mesh_devices))
    svc = solver_mod.BulkSolverService()
    monkeypatch.setattr(solver_mod, "_service", svc)
    return svc


def _run_pipeline(monkeypatch, mesh_devices: int, algorithm: str):
    """Full dequeue -> tensor build -> solve -> plan-apply -> commit on
    a fresh harness + fresh solver service -> parity fingerprint."""
    svc = _fresh_service(monkeypatch, mesh_devices)
    try:
        h = Harness()
        mock.build_nodes(h.store, 256)
        cfg = SchedulerConfiguration(scheduler_algorithm=algorithm)
        jobs = []
        for i, (count, cpu, mem) in enumerate(
                ((700, 50, 32), (900, 60, 48), (500, 80, 64))):
            j = mock.service_job(count, cpu=cpu, mem=mem, batch=True)
            j.id = f"parity-{algorithm}-{i}"  # pins the solver jitter seeds
            jobs.append(j)
        for i, j in enumerate(jobs):
            h.store.upsert_job(j)
            # pinned eval id -> pinned crc32 seed -> identical jitter on
            # every run, so parity is exact, not statistical
            h.process(mock.eval_for(j, id=f"parity-ev-{algorithm}-{i}"),
                      sched_config=cfg)
        snap = h.store.snapshot()
        # node NAMES come from a process-global mock counter and differ
        # between harness runs; the canonical registration ordinal is
        # the cross-run-stable identity (build_nodes registers the same
        # seeded sequence every time)
        ordinal = {n.id: i for i, n in enumerate(h.store.snapshot().nodes())}
        fingerprint = {}
        for j in jobs:
            per_node: dict = {}
            scores = []
            n_allocs = 0
            for a in snap.allocs_by_job(j.id):
                n_allocs += 1
                key = ordinal[a.node_id]
                per_node[key] = per_node.get(key, 0) + 1
                if a.metrics is not None:
                    scores.extend(
                        v for k, v in a.metrics.scores.items()
                        if k.endswith(".normalized-score"))
            fingerprint[j.id] = (n_allocs,
                                 tuple(sorted(per_node.items())),
                                 tuple(sorted(set(scores))))
        return fingerprint, dict(svc.stats)
    finally:
        svc.stop()


@pytest.mark.parametrize("algorithm", [enums.SCHED_ALG_TPU_BINPACK,
                                       enums.SCHED_ALG_TPU_SOLVE])
def test_e2e_parity_across_mesh_sizes(monkeypatch, algorithm, eight_devices):
    ref, ref_stats = _run_pipeline(monkeypatch, 1, algorithm)
    assert ref_stats["mesh_devices"] == 0  # capped to single-device
    assert ref_stats["sharded"] == 0
    total = sum(sum(c for _, c in fp[1]) for fp in ref.values())
    assert total == 700 + 900 + 500, ref
    for m in (2, 4, 8):
        got, stats = _run_pipeline(monkeypatch, m, algorithm)
        assert got == ref, f"mesh={m} diverged from single-device"
        assert stats["mesh_devices"] == m
        assert stats["sharded"] >= 3, stats
        assert stats["retraces"] == 0, stats
        if m == 8:
            # the gather accounting must be live on the sharded path
            assert stats["allgathers"] > 0, stats


def test_warm_sharded_launch_no_retrace(monkeypatch, eight_devices):
    """Once a sharded shape is warm, repeating it compiles nothing —
    the shape-keyed no_retrace window + explicit NamedSharding
    device_put satellite. A bare-array input would fork the jit cache
    (committed-vs-bare layouts) and show up as compile growth here."""
    svc = _fresh_service(monkeypatch, 8)
    try:
        h = Harness()
        mock.build_nodes(h.store, 256)
        cfg = SchedulerConfiguration(
            scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)

        def one(i):
            j = mock.service_job(300, cpu=50, mem=32, batch=True)
            j.id = f"warm-{i}"
            h.store.upsert_job(j)
            h.process(mock.eval_for(j, id=f"warm-ev-{i}"),
                      sched_config=cfg)

        one(0)
        assert svc.stats["sharded"] >= 1, svc.stats
        warm_compiles = svc.stats["compiles"]
        one(1)
        one(2)
        assert svc.stats["sharded"] >= 3, svc.stats
        assert svc.stats["compiles"] == warm_compiles, svc.stats
        assert svc.stats["retraces"] == 0, svc.stats
    finally:
        svc.stop()


def test_double_buffer_exact_fill_under_slow_apply(monkeypatch,
                                                   eight_devices):
    """4 committer threads x 5 solves race the pipelined service with a
    deliberately slow plan-apply between fetch and confirm, on a
    workload that EXACTLY fills the cluster (80 asks, 80 slots) with
    RESYNC_SOLVES=3 forcing carry rebuilds mid-stream. Any solve run
    against a stale carry overplaces (oversubscription), any resync
    that drops the unfetched launch or a correction double-books — both
    break exact fill. Also proves the double buffer actually engaged
    (stats["pipelined"] > 0 and measured overlap)."""
    from nomad_tpu.tensor.cluster import ClusterStatic
    from nomad_tpu.tensor.solver import BulkSolverService

    monkeypatch.setenv("NOMAD_TPU_MESH_DEVICES", "8")
    nodes = []
    for i in range(8):
        nd = mock.node()
        nd.name = f"db-n{i}"
        nd.resources.cpu = 1000       # fits exactly 10 x 100-cpu asks
        nd.resources.memory_mb = 8192
        nd.compute_class()
        nodes.append(nd)
    static = ClusterStatic(nodes)
    n_pad = static.n_pad
    feas = np.ones(n_pad, dtype=bool)
    aff = np.zeros(n_pad, dtype=np.float32)
    ask = np.zeros(RESOURCE_DIMS, dtype=np.float32)
    ask[0], ask[1] = 100.0, 64.0

    svc = BulkSolverService()
    svc.RESYNC_SOLVES = 3  # instance override: resync every few solves
    # commits are deferred to the end: used_fn stays all-zeros, so the
    # open ledger is the ONLY accounting a resync can rebuild from —
    # exactly the in-flight window the double buffer stretches
    zeros = np.zeros((n_pad, RESOURCE_DIMS), dtype=np.float32)
    placed_lock = threading.Lock()
    placed_total = np.zeros(n_pad, dtype=np.int64)
    tokens = []
    errors = []

    def committer(t):
        try:
            for i in range(5):
                counts, token = svc.solve(
                    static=static, feas_base=feas, aff=aff, ask=ask,
                    k=4, tg_count=1.0, seed=t * 100 + i,
                    used_fn=lambda: zeros, joint=False)
                time.sleep(0.02)  # slow plan-verify/apply
                with placed_lock:
                    placed_total[:] += counts
                    tokens.append(token)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=committer, args=(t,))
               for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    assert not errors, errors
    # exact fill: all 80 asks placed, no node above its 10-slot capacity.
    # A solve run against a stale carry — or a resync that rebuilt
    # without the unfetched launch's (ledger-less) usage — overplaces
    # some node past 10; a dropped request underplaces the total.
    assert int(placed_total.sum()) == 80, placed_total
    assert int(placed_total.max()) == 10, placed_total
    for token in tokens:
        svc.confirm(token, [])
    svc.stop()
    # every ledger entry closed by its confirm
    with svc._lock:
        assert not svc._ledger, dict(svc._ledger)
    assert svc.stats["resyncs"] >= 2, svc.stats
    # the double buffer engaged: at least one launch was fetched AFTER
    # its successor was dispatched
    assert svc.stats["pipelined"] >= 1, svc.stats


def test_inflight_drained_before_resync(monkeypatch, eight_devices):
    """RESYNC_SOLVES=1 makes EVERY dispatch rebuild the carry from
    used_fn + ledger. With the pipeline holding one unfetched launch at
    a time, a rebuild that skipped draining it would lose its usage and
    overplace on the exactly-filling workload below."""
    from nomad_tpu.tensor.cluster import ClusterStatic
    from nomad_tpu.tensor.solver import BulkSolverService

    monkeypatch.setenv("NOMAD_TPU_MESH_DEVICES", "8")
    nodes = []
    for i in range(8):
        nd = mock.node()
        nd.name = f"rs-n{i}"
        nd.resources.cpu = 500        # fits exactly 5 x 100-cpu asks
        nd.resources.memory_mb = 8192
        nd.compute_class()
        nodes.append(nd)
    static = ClusterStatic(nodes)
    feas = np.ones(static.n_pad, dtype=bool)
    aff = np.zeros(static.n_pad, dtype=np.float32)
    ask = np.zeros(RESOURCE_DIMS, dtype=np.float32)
    ask[0], ask[1] = 100.0, 32.0

    svc = BulkSolverService()
    svc.RESYNC_SOLVES = 1
    zeros = np.zeros((static.n_pad, RESOURCE_DIMS), dtype=np.float32)
    placed_lock = threading.Lock()
    placed = np.zeros(static.n_pad, dtype=np.int64)
    tokens = []
    errors = []

    def committer(t):
        try:
            for i in range(5):
                counts, token = svc.solve(
                    static=static, feas_base=feas, aff=aff, ask=ask,
                    k=2, tg_count=1.0, seed=t * 10 + i,
                    used_fn=lambda: zeros, joint=False)
                time.sleep(0.01)
                with placed_lock:
                    placed[:] += counts
                    tokens.append(token)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=committer, args=(t,))
               for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    assert not errors, errors
    # 4 threads x 5 solves x k=2 = 40 asks on exactly 40 slots
    assert int(placed.sum()) == 40, placed
    assert int(placed.max()) == 5, placed
    assert svc.stats["resyncs"] >= 5, svc.stats
    for token in tokens:
        svc.confirm(token, [])
    svc.stop()
    with svc._lock:
        assert not svc._ledger, dict(svc._ledger)
