"""Plan-applier scale machinery: parallel per-node verification,
pipelined verify-vs-commit overlay, bad-node quarantine
(reference nomad/plan_apply.go:70-95, plan_apply_pool.go:21,
plan_apply_node_tracker.go:17)."""

import time
from concurrent.futures import Future

import pytest

from nomad_tpu import mock
from nomad_tpu.core.plan_apply import (BadNodeTracker, PlanApplier, PlanQueue,
                                       _CommitEntry, _OverlaySnapshot)
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.state import StateStore
from nomad_tpu.structs import enums
from nomad_tpu.structs.plan import Plan
from nomad_tpu.structs.resources import NetworkResource


def applier(store, **kw):
    q = PlanQueue()
    q.set_enabled(True)
    return PlanApplier(store, q, **kw), q


class TestParallelVerify:
    def test_parallel_matches_serial(self):
        store = StateStore()
        job = mock.job()
        store.upsert_job(job)
        nodes = []
        for i in range(40):
            n = mock.node()
            if i % 3 == 0:  # every third node too small for the ask
                n.resources.cpu = 100
                n.resources.memory_mb = 64
            n.compute_class()
            store.upsert_node(n)
            nodes.append(n)
        plan = Plan(eval_id="e1", snapshot_index=store.latest_index)
        for i, n in enumerate(nodes):
            plan.append_alloc(mock.alloc(job, n, index=i))

        a_serial, _ = applier(store)
        # unstarted applier: pool is None -> serial path
        res_s, rej_s = a_serial._verify(plan, None)

        a_par, _ = applier(store)
        a_par.PARALLEL_THRESHOLD = 4
        a_par.start()
        try:
            res_p, rej_p = a_par._verify(plan, None)
        finally:
            a_par.stop()
        assert sorted(rej_s) == sorted(rej_p)
        assert set(res_s.node_allocation) == set(res_p.node_allocation)
        assert len(rej_s) == 14  # ceil(40/3) small nodes rejected


class TestOverlayPipeline:
    def test_overlay_sees_inflight_placements(self):
        store = StateStore()
        node = mock.node()
        node.resources.cpu = 1000
        node.resources.memory_mb = 1024
        node.compute_class()
        store.upsert_node(node)
        job = mock.job()
        store.upsert_job(job)
        ap, _ = applier(store)

        # plan A fills the node; its commit is "in flight"
        a1 = mock.alloc(job, node, index=0)
        a1.allocated_vec = mock.alloc(job, node, index=0).allocated_vec * 0 \
            + [900, 900, 0, 0]
        pa = Plan(eval_id="ea", snapshot_index=store.latest_index)
        pa.append_alloc(a1)
        result_a, rejected_a = ap._verify(pa, None)
        assert not rejected_a

        # plan B, verified against the overlay, must see A's usage and
        # reject the node even though A has not committed yet
        a2 = mock.alloc(job, node, index=1)
        a2.allocated_vec = a1.allocated_vec
        pb = Plan(eval_id="eb", snapshot_index=store.latest_index)
        pb.append_alloc(a2)
        _, rejected_b = ap._verify(pb, [result_a])
        assert rejected_b == [node.id]
        # without the overlay B would (wrongly) pass
        _, rejected_plain = ap._verify(pb, None)
        assert rejected_plain == []

    def test_overlay_snapshot_merges_updates(self):
        store = StateStore()
        node = mock.node()
        store.upsert_node(node)
        job = mock.job()
        store.upsert_job(job)
        a = mock.alloc(job, node, index=0)
        store.upsert_allocs([a])
        snap = store.snapshot()

        from nomad_tpu.structs.plan import PlanResult

        stopped = a.copy_for_update()
        stopped.desired_status = enums.ALLOC_DESIRED_STOP
        new = mock.alloc(job, node, index=1)
        result = PlanResult()
        result.node_update[node.id] = [stopped]
        result.node_allocation[node.id] = [new]
        ov = _OverlaySnapshot(snap, [result])
        got = {x.id: x for x in ov.allocs_by_node(node.id)}
        assert got[a.id].desired_status == enums.ALLOC_DESIRED_STOP
        assert new.id in got
        assert ov.node_by_id(node.id) is not None

    def test_commit_failure_poisons_overlay_descendants(self, monkeypatch):
        """If plan A's commit FAILS after later plans were verified
        against an overlay containing A's never-landed result, those
        plans must re-verify at commit time — even when they are not A's
        immediate successor (the advisor's round-3 finding)."""
        store = StateStore()
        node = mock.node()
        node.resources.cpu = 1000
        node.resources.memory_mb = 1024
        node.compute_class()
        store.upsert_node(node)
        job = mock.job()
        store.upsert_job(job)
        big = mock.alloc(job, node, index=0)
        big.allocated_vec = big.allocated_vec * 0 + [900, 900, 0, 0]
        store.upsert_allocs([big])
        ap, _ = applier(store)

        # plan A stops the 900-unit alloc, freeing the node
        pa = Plan(eval_id="ea", snapshot_index=store.latest_index)
        pa.append_stopped_alloc(big, "test stop")
        gen_a = ap._poison_gen
        result_a, rej_a = ap._verify(pa, None)
        assert not rej_a

        # plan C, verified while A's result is in the overlay, fills the
        # capacity A's stop would free
        new = mock.alloc(job, node, index=1)
        new.allocated_vec = new.allocated_vec * 0 + [900, 900, 0, 0]
        pc = Plan(eval_id="ec", snapshot_index=store.latest_index)
        pc.append_alloc(new)
        gen_c = ap._poison_gen
        result_c, rej_c = ap._verify(pc, [result_a])
        assert not rej_c

        # A's commit fails (transient raft failure): neither the batch
        # transaction nor its per-plan fallback lands the stop
        def commit(plan, result, rejected, gen, cell):
            entry = _CommitEntry(plan, result, rejected, gen, cell, Future())
            ap._commit_entries([entry])
            return entry.future.result(timeout=0)

        def boom(*a, **kw):
            raise RuntimeError("leadership lost")

        cell_a = {"result": result_a}
        with monkeypatch.context() as m:
            m.setattr(store, "upsert_plan_results_batch", boom)
            m.setattr(store, "upsert_plan_results", boom)
            with pytest.raises(RuntimeError):
                commit(pa, result_a, rej_a, gen_a, cell_a)
        assert ap._poison_gen != gen_c
        # the failed entry's overlay cell was emptied: readers that catch
        # the new generation must not see the never-landed stop either
        assert not cell_a["result"].node_update

        # C's commit must re-verify against the real store (big still
        # live) and reject the node instead of overcommitting
        out = commit(pc, result_c, rej_c, gen_c, {"result": result_c})
        assert out.rejected_nodes == [node.id]
        live = [a for a in store.snapshot().allocs_by_node(node.id)
                if not a.terminal_status()]
        from nomad_tpu.structs import allocs_fit

        fit, dim, _ = allocs_fit(node, live)
        assert fit, dim

    def test_volume_race_rejection_does_not_feed_bad_node_tracker(self):
        """Cross-node volume-claim races say nothing about node health;
        only per-node plan invalidity may quarantine a node."""
        from nomad_tpu.structs.volumes import Volume, VolumeRequest

        store = StateStore()
        n1, n2 = mock.node(), mock.node()
        for n in (n1, n2):
            n.compute_class()
            store.upsert_node(n)
        vol = Volume(id="v1", namespace="default",
                     access_mode="single-node-writer")
        store.upsert_volume(vol)
        job = mock.job()
        job.task_groups[0].volumes = {
            "data": VolumeRequest(name="data", type="csi", source="v1")}
        store.upsert_job(job)
        ap, _ = applier(store)
        plan = Plan(eval_id="e1", snapshot_index=store.latest_index)
        for i, n in enumerate((n1, n2)):
            a = mock.alloc(job, n, index=i)
            plan.append_alloc(a)
        _, rejected = ap._verify(plan, None)
        # one side loses the single-writer race...
        assert len(rejected) == 1
        # ...but the tracker holds no events for either node
        assert not ap.bad_nodes._events

    def test_pipelined_loop_end_to_end(self):
        """Plans streamed through the applier thread commit in order and
        answer their submitters."""
        store = StateStore()
        nodes = []
        for _ in range(8):
            n = mock.node()
            store.upsert_node(n)
            nodes.append(n)
        job = mock.job()
        store.upsert_job(job)
        ap, q = applier(store)
        ap.start()
        try:
            pendings = []
            for i, n in enumerate(nodes):
                p = Plan(eval_id=f"e{i}", snapshot_index=store.latest_index)
                p.append_alloc(mock.alloc(job, n, index=i))
                pendings.append(q.enqueue(p))
            results = [p.wait(timeout=10.0) for p in pendings]
            assert all(r.alloc_index > 0 for r in results)
            snap = store.snapshot()
            assert sum(1 for _ in snap.allocs()) == 8
        finally:
            ap.stop()


class TestBadNodeTracker:
    def test_threshold_fires_once_per_window(self):
        fired = []
        t = BadNodeTracker(threshold=3, window=60.0, on_bad_node=fired.append)
        now = 1000.0
        assert not t.add("n1", now)
        assert not t.add("n1", now + 1)
        assert t.add("n1", now + 2)
        assert fired == ["n1"]
        # window restarts after firing
        assert not t.add("n1", now + 3)

    def test_window_expiry(self):
        t = BadNodeTracker(threshold=2, window=10.0)
        assert not t.add("n1", 1000.0)
        assert not t.add("n1", 1011.0)  # first event expired
        assert t.add("n1", 1012.0)

    def test_server_quarantines_bad_node(self):
        cfg = ServerConfig(num_workers=0, heartbeat_ttl=3600,
                           gc_interval=3600,
                           plan_rejection_tracker_enabled=True,
                           plan_rejection_threshold=2,
                           plan_rejection_window=60.0)
        srv = Server(cfg)
        node = mock.node()
        node.resources.cpu = 100
        node.resources.memory_mb = 64
        node.compute_class()
        srv.store.upsert_node(node)
        job = mock.job()
        srv.store.upsert_job(job)
        with srv:
            for i in range(2):
                p = Plan(eval_id=f"e{i}",
                         snapshot_index=srv.store.latest_index)
                big = mock.alloc(job, node, index=i)  # 500MHz > 100MHz node
                p.append_alloc(big)
                pending = srv.plan_queue.enqueue(p)
                r = pending.wait(timeout=10.0)
                assert r.rejected_nodes == [node.id]
            deadline = time.time() + 5.0
            while time.time() < deadline:
                n = srv.store.snapshot().node_by_id(node.id)
                if n.scheduling_eligibility == enums.NODE_SCHED_INELIGIBLE:
                    break
                time.sleep(0.05)
            assert (srv.store.snapshot().node_by_id(node.id)
                    .scheduling_eligibility == enums.NODE_SCHED_INELIGIBLE)


class TestReservedPortRace:
    @pytest.mark.parametrize("algorithm", [enums.SCHED_ALG_BINPACK,
                                           enums.SCHED_ALG_TPU_BINPACK])
    def test_two_workers_race_one_reserved_port(self, algorithm):
        """Two jobs wanting the same static port on a one-node cluster,
        racing through two workers and the full applier loop: exactly one
        side holds the port afterwards; the loser blocks. This is the
        full-loop scenario the NetworkIndex design claims to handle
        (structs/network.py + plan re-verify)."""
        from nomad_tpu.structs.operator import SchedulerConfiguration

        cfg = ServerConfig(
            num_workers=2, heartbeat_ttl=3600, gc_interval=3600,
            nack_timeout=900.0,
            sched_config=SchedulerConfiguration(scheduler_algorithm=algorithm))
        srv = Server(cfg)
        node = mock.node()
        node.compute_class()
        srv.store.upsert_node(node)
        jobs = []
        for _ in range(2):
            j = mock.job()
            tg = j.task_groups[0]
            tg.count = 1
            tg.networks = [NetworkResource(
                mode="host", reserved_ports=[("http", 8080)])]
            jobs.append(j)
        with srv:
            for j in jobs:
                srv.register_job(j)
            srv.wait_for_idle(timeout=60.0, include_delayed=False)
            snap = srv.store.snapshot()
            holders = []
            for j in jobs:
                for a in snap.allocs_by_job(j.id):
                    if a.terminal_status():
                        continue
                    ports = [p.value for p in a.allocated_ports]
                    if 8080 in ports:
                        holders.append(a)
            assert len(holders) == 1, [h.id for h in holders]
            # committed state is collision-free by the applier invariant
            from nomad_tpu.structs import allocs_fit

            live = [a for a in snap.allocs_by_node(node.id)
                    if not a.terminal_status()]
            fit, dim, _ = allocs_fit(node, live)
            assert fit, dim
