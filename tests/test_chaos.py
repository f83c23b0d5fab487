"""Chaos scenarios: scripted faults against the replicated control
plane, safety invariants checked between steps (nomad_tpu/chaos/).

Each scenario is deterministic under a fixed seed; set
NOMAD_TPU_CHAOS_SEED to replay a randomized-sweep failure.
"""

import logging
import os
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import (
    FaultPlan,
    FSFaults,
    InvariantChecker,
    ScenarioRunner,
    tear_log_tail,
    truncate_log_mid_line,
)
from nomad_tpu.core.server import ServerConfig
from nomad_tpu.raft.cluster import RaftCluster
from nomad_tpu.raft.node import NotLeaderError
from nomad_tpu.structs import enums


def _wait(predicate, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _live_entry(cluster, exclude=()):
    return next(s for s in cluster.servers.values()
                if not s.crashed and s.id not in exclude)


# ---------------------------------------------------------------------------
# the fault plan itself
# ---------------------------------------------------------------------------


class TestFaultPlanDeterminism:
    def test_same_seed_same_verdicts(self):
        def verdicts(seed):
            p = FaultPlan(seed=seed)
            p.set_link_faults(drop=0.2, delay=0.3, duplicate=0.2,
                              reorder=0.1)
            return [p.decide("a", "b") for _ in range(200)]

        assert verdicts(42) == verdicts(42)
        assert verdicts(42) != verdicts(43)

    def test_interleaving_independent(self):
        # verdict for message #n on a link depends only on (seed, link, n),
        # not on traffic elsewhere
        p1 = FaultPlan(seed=9)
        p1.set_link_faults(drop=0.5)
        a = [p1.decide("x", "y") for _ in range(50)]
        p2 = FaultPlan(seed=9)
        p2.set_link_faults(drop=0.5)
        for _ in range(50):
            p2.decide("x", "z")  # unrelated-link traffic in between
        b = [p2.decide("x", "y") for _ in range(50)]
        assert a == b

    def test_scripted_cut_is_exact_and_expires(self):
        t = [0.0]
        p = FaultPlan(seed=0, clock=lambda: t[0])
        p.cut_link("a", "b", for_s=5.0)
        assert p.decide("a", "b").drop
        assert not p.decide("b", "a").drop  # directed
        t[0] = 6.0
        assert not p.decide("a", "b").drop  # auto-healed


# ---------------------------------------------------------------------------
# scenario 1: directed partition
# ---------------------------------------------------------------------------


class TestDirectedPartition:
    def test_leader_outbound_cut_elects_new_leader(self):
        with RaftCluster(3) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            leader = r.wait_for_leader()
            entry = _live_entry(cluster)
            entry.register_node(mock.node())
            others = [sid for sid in cluster.servers if sid != leader.id]
            for sid in others:
                cluster.transport.partition_link(leader.id, sid)
            # followers stop hearing heartbeats and elect among
            # themselves; the inbound direction is open, so the old
            # leader hears the higher term and steps down
            _wait(lambda: any(cluster.servers[sid].raft.is_leader()
                              for sid in others),
                  msg="replacement leader")
            _wait(lambda: not leader.raft.is_leader(),
                  msg="old leader stepping down")
            r.checker.check_all(cluster)
            # writes keep flowing through the new leader
            _live_entry(cluster, exclude=(leader.id,)).register_node(
                mock.node())
            r.heal_and_converge()


# ---------------------------------------------------------------------------
# scenario 2: message-level faults (drop/delay/duplicate/reorder)
# ---------------------------------------------------------------------------


class TestMessageFaults:
    def test_cluster_survives_fault_soup(self):
        with RaftCluster(3) as cluster:
            r = ScenarioRunner(cluster, seed=7)
            r.plan.set_link_faults(drop=0.08, delay=0.25, duplicate=0.10,
                                   reorder=0.05, delay_range=(0.001, 0.01))
            leader = r.wait_for_leader()
            entry = _live_entry(cluster)
            for _ in range(4):
                entry.register_node(mock.node())
            job = mock.job()
            job.task_groups[0].count = 3
            entry.register_job(job)
            leader.server.wait_for_idle(20.0)
            r.checker.check_all(cluster)
            stats = r.plan.snapshot_stats()
            assert stats["delivered"] > 0
            # the soup actually bit: at least one fault class fired
            assert (stats["dropped"] + stats["delayed"]
                    + stats["duplicated"] + stats["reordered"]) > 0
            r.heal_and_converge()
            assert len(cluster.leader().store.snapshot()
                       .allocs_by_job(job.id)) >= 3


# ---------------------------------------------------------------------------
# scenario 3: leader crash-restart mid-commit (durable)
# ---------------------------------------------------------------------------


class TestCrashRestart:
    def test_leader_crash_mid_commit_loses_nothing(self, tmp_path):
        with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            leader = r.wait_for_leader()
            victim = leader.id
            stop = threading.Event()
            accepted = []

            def writer():
                entry = _live_entry(cluster, exclude=(victim,))
                while not stop.is_set():
                    n = mock.node()
                    try:
                        entry.register_node(n)
                        accepted.append(n.id)
                    except (NotLeaderError, TimeoutError):
                        pass  # crash window; the chaos point is that
                        # *acknowledged* writes survive, not that every
                        # attempt lands
                    time.sleep(0.01)

            t = threading.Thread(target=writer, daemon=True)
            t.start()
            time.sleep(0.3)
            cluster.crash(victim)
            _wait(lambda: cluster.leader() is not None,
                  msg="new leader after crash")
            time.sleep(0.3)  # writes keep landing on the new leader
            cluster.restart(victim)
            time.sleep(0.3)
            stop.set()
            t.join(timeout=5)
            r.checker.check_all(cluster)
            r.heal_and_converge(timeout=20.0)
            # every acknowledged registration survived the crash
            snap = cluster.leader().store.snapshot()
            present = {n.id for n in snap.nodes()}
            missing = [nid for nid in accepted if nid not in present]
            assert not missing, f"acked writes lost across crash: {missing}"
            assert len(accepted) > 5  # the writer actually exercised this

    def test_restarted_node_rejoins_and_catches_up(self, tmp_path):
        with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            r.wait_for_leader()
            follower = cluster.followers()[0]
            entry = _live_entry(cluster, exclude=(follower.id,))
            entry.register_node(mock.node())
            cluster.crash(follower.id)
            for _ in range(3):  # history the dead node must replay
                entry.register_node(mock.node())
            cluster.restart(follower.id)
            r.checker.check_all(cluster)
            r.heal_and_converge(timeout=20.0)


# ---------------------------------------------------------------------------
# scenario 4: torn/corrupt durable log on restart
# ---------------------------------------------------------------------------


class TestTornLogRestart:
    def test_torn_tail_does_not_brick_restart(self, tmp_path, caplog):
        with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            r.wait_for_leader()
            entry = _live_entry(cluster)
            for _ in range(3):
                entry.register_node(mock.node())
            follower = cluster.followers()[0]
            cluster.crash(follower.id)
            # a crash mid-append leaves a half-written last line
            tear_log_tail(os.path.join(follower.data_dir, "raft"))
            with caplog.at_level(logging.WARNING, logger="nomad_tpu.raft"):
                cluster.restart(follower.id)
            assert any("torn tail" in rec.message for rec in caplog.records)
            r.checker.check_all(cluster)
            r.heal_and_converge(timeout=20.0)

    def test_truncated_mid_line_recovers_too(self, tmp_path):
        with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            r.wait_for_leader()
            entry = _live_entry(cluster)
            for _ in range(3):
                entry.register_node(mock.node())
            follower = cluster.followers()[0]
            cluster.crash(follower.id)
            truncate_log_mid_line(os.path.join(follower.data_dir, "raft"))
            cluster.restart(follower.id)
            r.checker.check_all(cluster)
            r.heal_and_converge(timeout=20.0)


# ---------------------------------------------------------------------------
# scenario 5: heartbeat invalidation reschedules work
# ---------------------------------------------------------------------------


def _short_ttl(_i):
    return ServerConfig(heartbeat_ttl=0.4)


class TestHeartbeatChaos:
    def test_silent_node_invalidated_and_rescheduled(self):
        with RaftCluster(3, config_fn=_short_ttl) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            leader = r.wait_for_leader()
            entry = _live_entry(cluster)
            n1, n2 = mock.node(), mock.node()
            entry.register_node(n1)
            entry.register_node(n2)
            job = mock.job()
            job.task_groups[0].count = 2
            entry.register_job(job)
            leader.server.wait_for_idle(15.0)
            # n2 keeps heartbeating; n1 goes silent and misses its TTL
            _wait(lambda: (entry.heartbeat(n2.id),
                           cluster.leader().store.snapshot()
                           .node_by_id(n1.id).status
                           == enums.NODE_STATUS_DOWN)[1],
                  interval=0.05, msg="silent node marked down")
            r.checker.check_reschedule(cluster.leader(), timeout=15.0)
            r.checker.check_all(cluster)
            live = [a for a in cluster.leader().store.snapshot()
                    .allocs_by_job(job.id)
                    if not a.terminal_status() and not a.server_terminal()]
            assert live and all(a.node_id == n2.id for a in live)

    def test_new_leader_rearms_ttls_after_failover(self):
        # regression: a client that goes silent DURING a leader failover
        # must still be invalidated — its TTL timer lived only on the
        # old leader, so the new one re-arms from replicated state
        # (core/server.py _restore_heartbeats)
        with RaftCluster(3, config_fn=_short_ttl) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            leader = r.wait_for_leader()
            entry = _live_entry(cluster, exclude=(leader.id,))
            n1, n2 = mock.node(), mock.node()
            entry.register_node(n1)
            entry.register_node(n2)
            job = mock.job()
            job.task_groups[0].count = 2
            entry.register_job(job)
            leader.server.wait_for_idle(15.0)
            cluster.crash(leader.id)
            _wait(lambda: cluster.leader() is not None,
                  msg="new leader after crash")
            # n1 never heartbeats again; n2 stays chatty
            _wait(lambda: (entry.heartbeat(n2.id),
                           cluster.leader().store.snapshot()
                           .node_by_id(n1.id).status
                           == enums.NODE_STATUS_DOWN)[1],
                  interval=0.05, timeout=15.0,
                  msg="new leader invalidating the silent node")
            r.checker.check_reschedule(cluster.leader(), timeout=15.0)
            r.checker.check_all(cluster)


# ---------------------------------------------------------------------------
# scenario 6: full-cluster mayhem, then heal-and-converge
# ---------------------------------------------------------------------------


class TestHealAndConverge:
    def test_everything_at_once_then_heal(self, tmp_path):
        with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=3)
            leader = r.wait_for_leader()
            entry = _live_entry(cluster)
            entry.register_node(mock.node())
            # soup + a directed cut + a follower crash-restart
            r.plan.set_link_faults(drop=0.05, delay=0.2, duplicate=0.05,
                                   delay_range=(0.001, 0.01))
            follower = cluster.followers()[0]
            cluster.transport.partition_link(leader.id, follower.id)
            cluster.crash(follower.id)
            for _ in range(3):
                _live_entry(cluster, exclude=(follower.id,)).register_node(
                    mock.node())
            r.checker.check_all(cluster)
            cluster.restart(follower.id)
            r.checker.check_all(cluster)
            r.heal_and_converge(timeout=25.0)


# ---------------------------------------------------------------------------
# scenario 7: disk faults (ENOSPC) at the durable-log chokepoint
# ---------------------------------------------------------------------------


class TestDiskFaults:
    def test_enospc_append_fails_cleanly_and_recovers(self, tmp_path):
        with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            leader = r.wait_for_leader()
            fs = FSFaults()
            fs.arm("log_append", count=1, path_substr=leader.id)
            with fs.installed():
                with pytest.raises(OSError):
                    leader.server.register_node(mock.node())
            assert fs.stats["raised"] == 1
            # the failed append rolled back in memory: the next write
            # must land at the same index, not leave a gap/divergence
            leader = r.wait_for_leader()
            _live_entry(cluster).register_node(mock.node())
            r.checker.check_all(cluster)
            # and the durable file agrees after a crash-restart
            victim = leader.id
            cluster.crash(victim)
            cluster.restart(victim)
            r.checker.check_all(cluster)
            r.heal_and_converge(timeout=20.0)

    def test_atomic_write_fault_leaves_old_state(self, tmp_path):
        from nomad_tpu.raft.durable import StableStore
        store = StableStore(str(tmp_path))
        store.save(3, "node-a")
        fs = FSFaults()
        fs.arm("atomic_write_text", count=1)
        with fs.installed():
            with pytest.raises(OSError):
                store.save(4, "node-b")
        # memory never claimed a persistence that didn't happen
        assert (store.term, store.voted_for) == (3, "node-a")
        reloaded = StableStore(str(tmp_path))
        assert (reloaded.term, reloaded.voted_for) == (3, "node-a")


# ---------------------------------------------------------------------------
# scenario 8: the batched write path (group commit + pipelined
# replication, ISSUE 4) under the PR 3 fault model
# ---------------------------------------------------------------------------


class TestBatchedWritePath:
    def test_crash_mid_batch_append_loses_no_acked_writes(self, tmp_path):
        """Concurrent proposers keep the log-writer's batches full; the
        leader dies mid-stream and its log tail is torn mid-line (the
        disk state a crash inside a batched write leaves). Recovery must
        drop only the un-fsynced suffix — every ACKED write survives,
        because an ack requires the whole batch fsynced + committed."""
        with RaftCluster(3, data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=11)
            leader = r.wait_for_leader()
            victim = leader.id
            stop = threading.Event()
            accepted = []
            acc_lock = threading.Lock()

            def writer():
                entry = _live_entry(cluster, exclude=(victim,))
                while not stop.is_set():
                    n = mock.node()
                    try:
                        entry.register_node(n)
                        with acc_lock:
                            accepted.append(n.id)
                    except (NotLeaderError, TimeoutError):
                        pass  # ambiguous during the crash window

            threads = [threading.Thread(target=writer, daemon=True)
                       for _ in range(4)]
            for t in threads:
                t.start()
            time.sleep(0.4)
            cluster.crash(victim)
            tear_log_tail(os.path.join(
                cluster.servers[victim].data_dir, "raft"))
            _wait(lambda: cluster.leader() is not None,
                  msg="new leader after mid-batch crash")
            time.sleep(0.3)
            cluster.restart(victim)
            time.sleep(0.2)
            stop.set()
            for t in threads:
                t.join(timeout=5)
            r.checker.check_all(cluster)
            r.heal_and_converge(timeout=25.0)
            snap = cluster.leader().store.snapshot()
            present = {n.id for n in snap.nodes()}
            missing = [nid for nid in accepted if nid not in present]
            assert not missing, f"acked writes lost mid-batch: {missing}"
            assert len(accepted) > 20  # proposers actually formed batches

    def test_partition_mid_pipeline_converges(self):
        """Directed cuts land while the per-peer replicators are mid-
        pipeline: the cut peer's replicator backs off, the quorum keeps
        committing, and heal converges every FSM (log matching holds —
        no entry the cut follower acked can be rolled back)."""
        with RaftCluster(3) as cluster:
            r = ScenarioRunner(cluster, seed=13)
            leader = r.wait_for_leader()
            stop = threading.Event()
            accepted = []
            acc_lock = threading.Lock()

            def writer():
                entry = _live_entry(cluster)
                while not stop.is_set():
                    n = mock.node()
                    try:
                        entry.register_node(n)
                        with acc_lock:
                            accepted.append(n.id)
                    except (NotLeaderError, TimeoutError):
                        pass

            threads = [threading.Thread(target=writer, daemon=True)
                       for _ in range(4)]
            for t in threads:
                t.start()
            # cut one replication pipeline at a time, mid-flight; the
            # remaining follower keeps the quorum
            followers = [s.id for s in cluster.followers()]
            for fid in followers:
                cluster.transport.partition_link(leader.id, fid)
                time.sleep(0.25)
                cluster.transport.heal_link(leader.id, fid)
                time.sleep(0.1)
            r.checker.check_all(cluster)
            stop.set()
            for t in threads:
                t.join(timeout=5)
            r.heal_and_converge(timeout=25.0)
            assert accepted, "no write survived the pipeline cuts"
            snap = cluster.leader().store.snapshot()
            present = {n.id for n in snap.nodes()}
            missing = [nid for nid in accepted if nid not in present]
            assert not missing, f"acked writes lost mid-pipeline: {missing}"

    def test_torn_batch_tail_recovers_to_line_boundary(self, tmp_path):
        """A batch is one buffered write: a crash mid-write tears the
        LAST line, and recovery keeps the intact prefix of the batch
        (safe: commit requires the whole batch fsynced, so nothing in
        a torn suffix was ever acked)."""
        from nomad_tpu.raft.durable import DurableLog

        d = str(tmp_path)
        log = DurableLog(d)
        batch = log.append_batch(1, [("compact", (i,), {})
                                     for i in range(6)])
        assert [e.index for e in batch] == [1, 2, 3, 4, 5, 6]
        log.close()
        truncate_log_mid_line(d)
        log2 = DurableLog(d)
        last_index, last_term = log2.last()
        assert last_term == 1 and last_index == 5, \
            "torn batch tail must drop exactly the torn suffix"
        assert [e.index for e in log2.slice_from(1, 100)] == [1, 2, 3, 4, 5]
        # and the next batch lands cleanly after the boundary
        cont = log2.append_batch(1, [("compact", (99,), {})])
        assert cont[0].index == 6
        log2.close()


# ---------------------------------------------------------------------------
# randomized sweep (slow; seed printed for replay)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestRandomizedSweep:
    def test_random_fault_sweep(self, tmp_path):
        import random
        seed = int(os.environ.get("NOMAD_TPU_CHAOS_SEED", "0") or 0)
        rng = random.Random(seed)
        for round_no in range(3):
            sub_seed = rng.randrange(1 << 30)
            with RaftCluster(3, data_dir=str(tmp_path / str(round_no))) \
                    as cluster:
                # fresh checker per round: history invariants are scoped
                # to one cluster's lifetime
                r = ScenarioRunner(cluster, seed=sub_seed,
                                   checker=InvariantChecker())
                r.plan.set_link_faults(
                    drop=rng.uniform(0, 0.15),
                    delay=rng.uniform(0, 0.3),
                    duplicate=rng.uniform(0, 0.15),
                    reorder=rng.uniform(0, 0.08),
                    delay_range=(0.001, 0.01))
                leader = r.wait_for_leader(timeout=20.0)
                entry = _live_entry(cluster)
                for _ in range(rng.randrange(2, 6)):
                    entry.register_node(mock.node())
                if rng.random() < 0.7:
                    victim = rng.choice(
                        [s.id for s in cluster.followers()] or
                        [leader.id])
                    cluster.crash(victim)
                    time.sleep(rng.uniform(0.1, 0.4))
                    cluster.restart(victim)
                r.checker.check_all(cluster)
                r.heal_and_converge(timeout=30.0)


# ---------------------------------------------------------------------------
# scenario 8: leader crash mid-plan-batch-commit (ISSUE 5)
# ---------------------------------------------------------------------------


def _batched_pipeline_cfg(_i):
    """The full batched pipeline: 4 workers draining evals in bulk,
    plan-commit batching + pipelined commit rounds on, background
    timers parked so the scenario only exercises the eval pipeline."""
    return ServerConfig(
        num_workers=4, eval_batch_size=8,
        heartbeat_ttl=3600.0, gc_interval=3600.0, nack_timeout=900.0,
        failed_eval_followup_delay=3600.0,
        failed_eval_unblock_interval=0.5)


class TestLeaderCrashMidPlanBatchCommit:
    def test_acked_allocs_survive_unacked_evals_requeue(self, tmp_path):
        """Crash the leader while batched commit rounds are in flight:
        every alloc committed in the leader's FSM (= acked to its plan
        submitter) must survive the failover, no slot may end up with
        duplicate live allocs (the fallback re-apply is idempotent),
        and every eval the old leader never acked must be re-enqueued
        and drained by the new leader (_restore_evals)."""
        jobs_n = 60
        with RaftCluster(3, config_fn=_batched_pipeline_cfg,
                         data_dir=str(tmp_path)) as cluster:
            r = ScenarioRunner(cluster, seed=0)
            leader = r.wait_for_leader()
            for _ in range(12):
                leader.register_node(mock.node())
            jobs = []
            for _ in range(jobs_n):
                j = mock.job()
                j.task_groups[0].count = 1
                j.task_groups[0].tasks[0].resources.cpu = 100
                j.task_groups[0].tasks[0].resources.memory_mb = 64
                jobs.append(j)
                leader.store.upsert_job(j)
            evals = [mock.eval_for(j, create_time=time.time())
                     for j in jobs]
            index = leader.store.upsert_evals(evals)
            for ev in evals:
                ev.modify_index = index
            for ev in evals:
                leader.server.broker.enqueue(ev)

            # the crash must land mid-stream: some batches committed,
            # many evals still in flight on the old leader's workers
            _wait(lambda: len(list(leader.local_store.snapshot()
                                   .allocs())) >= jobs_n // 4,
                  timeout=30.0, interval=0.002,
                  msg="mid-batch crash window")
            acked = {a.id for a in leader.local_store.snapshot().allocs()}
            cluster.crash(leader.id)

            _wait(lambda: cluster.leader() is not None, timeout=20.0,
                  msg="new leader after mid-batch crash")
            cluster.restart(leader.id)

            def drained():
                fresh = cluster.leader()
                if fresh is None or not fresh.server._running:
                    return False
                if not fresh.server.wait_for_idle(timeout=5.0,
                                                  include_delayed=False):
                    return False
                if fresh.server.blocked.blocked_count() != 0:
                    return False
                live = [a for a in fresh.local_store.snapshot().allocs()
                        if not a.terminal_status()
                        and not a.server_terminal()]
                return len(live) >= jobs_n

            _wait(drained, timeout=120.0, interval=0.1,
                  msg="pipeline drained after failover")

            r.checker.check_convergence(cluster, timeout=30.0)
            r.checker.check_alloc_uniqueness(cluster)
            r.checker.check_all(cluster)

            snap = cluster.leader().local_store.snapshot()
            lost = acked - {a.id for a in snap.allocs()}
            assert not lost, \
                f"acked allocs lost across failover: {sorted(lost)[:5]}"
            stranded = [e.id for e in snap.evals() if e.should_enqueue()]
            assert not stranded, \
                f"evals stranded pending after failover: {stranded[:5]}"
            assert len(acked) >= jobs_n // 4  # really was mid-stream


# ---------------------------------------------------------------------------
# scenario: chunked install-snapshot transfer under network/process chaos
# ---------------------------------------------------------------------------


class TestSnapshotTransferChaos:
    def test_wiped_follower_catches_up_through_dropped_frames(self, tmp_path):
        """A follower that lost its disk can only recover via the
        chunked install path; with frames dropped in transit the sender
        must resume from the follower-reported offset until the whole
        body lands and the digest verifies."""
        import shutil

        with RaftCluster(3, data_dir=str(tmp_path),
                         snapshot_threshold=10) as cluster:
            r = ScenarioRunner(cluster, seed=3)
            leader = r.wait_for_leader()
            for s in cluster.servers.values():
                s.raft.snapshot_chunk_bytes = 128  # force many frames
            nodes = [mock.node() for _ in range(30)]
            for n in nodes:
                leader.register_node(n)
            _wait(lambda: leader.raft.log.base_index > 0, 10.0,
                  msg="leader compaction")
            leader_base = leader.raft.log.base_index
            victim = cluster.followers()[0]
            cluster.crash(victim.id)
            shutil.rmtree(os.path.join(victim.data_dir, "raft"))
            r.plan.set_link_faults(src=leader.id, dst=victim.id, drop=0.2)
            cluster.restart(victim.id)
            victim = cluster.servers[victim.id]

            def caught_up():
                return (len(list(victim.local_store.snapshot().nodes()))
                        == len(nodes))
            _wait(caught_up, 30.0,
                  msg="wiped follower catch-up through dropped frames")
            # an empty log cannot replay compacted entries: only the
            # install path reaches a compacted base
            assert victim.raft.log.base_index >= leader_base
            assert r.plan.snapshot_stats()["dropped"] > 0, \
                "the drop faults never bit — transfer not exercised"
            r.heal_and_converge(timeout=20.0)
            r.checker.check_all(cluster)

    def test_leader_crash_mid_transfer_completes_from_new_leader(
            self, tmp_path):
        """Crash the leader while an install transfer is in flight: the
        half-accumulated sink on the follower is superseded and the new
        leader's transfer completes the catch-up (or, had no new leader
        compacted, plain replication would — either way the follower
        must converge with no torn state)."""
        import shutil

        with RaftCluster(3, data_dir=str(tmp_path),
                         snapshot_threshold=10) as cluster:
            r = ScenarioRunner(cluster, seed=4)
            leader = r.wait_for_leader()
            for s in cluster.servers.values():
                s.raft.snapshot_chunk_bytes = 64
            nodes = [mock.node() for _ in range(30)]
            for n in nodes:
                leader.register_node(n)
            _wait(lambda: all(s.raft.log.base_index > 0
                              for s in cluster.servers.values()), 10.0,
                  msg="every replica compacted")
            victim = cluster.followers()[0]
            cluster.crash(victim.id)
            shutil.rmtree(os.path.join(victim.data_dir, "raft"))
            # heavy drops stretch the transfer so the crash lands inside
            r.plan.set_link_faults(src=leader.id, dst=victim.id, drop=0.6)
            cluster.restart(victim.id)
            victim = cluster.servers[victim.id]
            _wait(lambda: victim.raft._snap_rx is not None
                  or victim.raft.log.base_index > 0, 15.0,
                  msg="transfer reached the follower")
            old_leader = leader.id
            cluster.crash(old_leader)
            r.plan.clear_faults()
            _wait(lambda: cluster.leader() is not None
                  and cluster.leader().id != old_leader, 20.0,
                  msg="new leader after crash")

            def caught_up():
                return (len(list(victim.local_store.snapshot().nodes()))
                        == len(nodes))
            _wait(caught_up, 30.0, msg="catch-up completed by new leader")
            assert victim.raft.log.base_index > 0
            cluster.restart(old_leader)
            r.heal_and_converge(timeout=20.0)
            r.checker.check_all(cluster)
