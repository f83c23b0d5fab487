"""Raft replication tests: election, log replication, failover, and the
replicated control plane scheduling end to end — all in-process
(the reference's multi-server test topology, SURVEY.md §4.3).
"""

import time

import pytest

from nomad_tpu import mock
from nomad_tpu.raft import RaftCluster, RaftNode
from nomad_tpu.raft.node import NotLeaderError
from nomad_tpu.raft.transport import InProcTransport
from nomad_tpu.structs import enums


# ---------------------------------------------------------------------------
# raw raft
# ---------------------------------------------------------------------------


def _mini_cluster(n=3, applied=None):
    transport = InProcTransport()
    ids = [f"n{i}" for i in range(n)]
    applied = applied if applied is not None else {i: [] for i in ids}
    nodes = {}
    for node_id in ids:
        log = applied.setdefault(node_id, [])

        def make_apply(l):
            def apply(cmd):
                l.append(cmd)
                return len(l)
            return apply

        nodes[node_id] = RaftNode(node_id, ids, transport, make_apply(log),
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


class TestRaftCore:
    def test_election_and_replication(self):
        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            for i in range(5):
                leader.apply(("compact", (i,), {}))
            deadline = time.time() + 5
            while time.time() < deadline:
                if all(len(l) == 5 for l in applied.values()):
                    break
                time.sleep(0.02)
            assert all(len(l) == 5 for l in applied.values())
            assert all(l == applied[leader.id] for l in applied.values())
        finally:
            for n in nodes.values():
                n.stop()

    def test_follower_rejects_apply(self):
        transport, nodes, _ = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            follower = next(n for n in nodes.values() if n is not leader)
            with pytest.raises(NotLeaderError):
                follower.apply(("compact", (), {}))
        finally:
            for n in nodes.values():
                n.stop()

    def test_asymmetric_link_cut_deposes_leader(self):
        # cut only the leader's OUTBOUND links: followers stop hearing
        # heartbeats and elect a new leader; the old leader still hears
        # the higher term on its open inbound side and steps down — the
        # asymmetric failure real networks produce (one-way firewall,
        # half-broken NIC)
        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            others = [i for i in nodes if i != leader.id]
            for i in others:
                transport.partition_link(leader.id, i)
            remaining = {k: v for k, v in nodes.items() if k != leader.id}
            new_leader = _wait_leader(remaining)
            assert new_leader.id != leader.id
            deadline = time.time() + 5
            while time.time() < deadline and leader.is_leader():
                time.sleep(0.02)
            assert not leader.is_leader()
            # directed heal: reopen the old leader's outbound side
            for i in others:
                transport.heal_link(leader.id, i)
            new_leader.apply(("compact", ("x",), {}))
            deadline = time.time() + 5
            while time.time() < deadline:
                if applied[leader.id] == applied[new_leader.id] != []:
                    break
                time.sleep(0.02)
            assert applied[leader.id] == applied[new_leader.id]
        finally:
            for n in nodes.values():
                n.stop()

    def test_heal_with_no_args_clears_links_and_partitions(self):
        transport, nodes, _ = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            other = next(i for i in nodes if i != leader.id)
            transport.partition(other)
            transport.partition_link(leader.id, other)
            assert transport.send(leader.id, other, {"kind": "ping"}) is None
            transport.heal()  # no args: everything
            leader.apply(("compact", ("y",), {}))  # replication works again
        finally:
            for n in nodes.values():
                n.stop()

    def test_leader_failover_and_catchup(self):
        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            leader.apply(("compact", ("a",), {}))
            transport.partition(leader.id)
            remaining = {k: v for k, v in nodes.items() if k != leader.id}
            new_leader = _wait_leader(remaining)
            assert new_leader.id != leader.id
            new_leader.apply(("compact", ("b",), {}))
            # heal: the old leader steps down and catches up
            transport.heal(leader.id)
            deadline = time.time() + 5
            while time.time() < deadline:
                if len(applied[leader.id]) == 2 and not leader.is_leader():
                    break
                time.sleep(0.02)
            assert applied[leader.id] == applied[new_leader.id]
            assert not leader.is_leader()
        finally:
            for n in nodes.values():
                n.stop()


# ---------------------------------------------------------------------------
# replicated control plane
# ---------------------------------------------------------------------------


class TestReplicatedServer:
    def test_schedules_through_replicated_log(self):
        with RaftCluster(3) as cluster:
            leader = cluster.wait_for_leader()
            assert leader is not None
            # any server accepts the request (forwarding)
            entry = cluster.any_server()
            entry.register_node(mock.node())
            entry.register_node(mock.node())
            job = mock.job()
            entry.register_job(job)
            assert leader.server.wait_for_idle(15.0)
            # every replica converges to the same placements
            deadline = time.time() + 10
            while time.time() < deadline:
                counts = [len(s.local_store.snapshot().allocs_by_job(job.id))
                          for s in cluster.servers.values()]
                if counts == [10, 10, 10]:
                    break
                time.sleep(0.05)
            assert counts == [10, 10, 10]
            # replicas agree on indexes too (determinism); allow the last
            # entries to finish replicating
            deadline = time.time() + 5
            while time.time() < deadline:
                idxs = {s.local_store.latest_index
                        for s in cluster.servers.values()}
                if len(idxs) == 1:
                    break
                time.sleep(0.05)
            assert len(idxs) == 1, idxs

    def test_leader_failover_cluster_keeps_scheduling(self):
        with RaftCluster(3) as cluster:
            leader = cluster.wait_for_leader()
            entry = cluster.any_server()
            entry.register_node(mock.node())
            job1 = mock.job()
            job1.task_groups[0].count = 2  # leave headroom for job2
            entry.register_job(job1)
            assert leader.server.wait_for_idle(15.0)

            # kill the leader (partition it away)
            cluster.transport.partition(leader.raft.id)
            deadline = time.time() + 10
            new_leader = None
            while time.time() < deadline:
                cands = [s for s in cluster.servers.values()
                         if s is not leader and s.is_leader()]
                if cands:
                    new_leader = cands[0]
                    break
                time.sleep(0.05)
            assert new_leader is not None

            # the cluster still schedules new jobs
            job2 = mock.job()
            job2.task_groups[0].count = 2
            new_leader.register_job(job2)
            assert new_leader.server.wait_for_idle(15.0)
            allocs = new_leader.local_store.snapshot().allocs_by_job(job2.id)
            assert len(allocs) == 2


class TestAdviceRegressions:
    """Round-2 fixes from ADVICE.md: vote safety + leader barrier."""

    def test_same_term_stepdown_keeps_vote(self):
        """A candidate stepping down on a same-term AppendEntries must not
        erase its self-vote (it could otherwise grant a second vote in the
        same term, electing two leaders)."""
        transport = InProcTransport()
        node = RaftNode("a", ["a", "b", "c"], transport, lambda c: None,
                        election_timeout=999, heartbeat_interval=999)
        node.current_term = 5
        node.state = "candidate"
        node.voted_for = "a"
        # same-term heartbeat from the elected leader
        reply = node.handle({"kind": "append_entries", "term": 5,
                             "leader": "b", "prev_log_index": 0,
                             "prev_log_term": 0, "entries": [],
                             "leader_commit": 0})
        assert reply["success"]
        assert node.state == "follower"
        assert node.voted_for == "a"  # vote retained for term 5
        # so a competing candidate in the same term is refused
        reply = node.handle({"kind": "request_vote", "term": 5,
                             "candidate": "c", "last_log_index": 0,
                             "last_log_term": 0})
        assert not reply["granted"]

    def test_vote_cleared_on_term_increase(self):
        transport = InProcTransport()
        node = RaftNode("a", ["a", "b"], transport, lambda c: None,
                        election_timeout=999, heartbeat_interval=999)
        node.current_term = 5
        node.voted_for = "a"
        reply = node.handle({"kind": "request_vote", "term": 6,
                             "candidate": "b", "last_log_index": 0,
                             "last_log_term": 0})
        assert reply["granted"] and node.voted_for == "b"

    def test_leader_barrier_commits_prior_term_entries(self):
        """Entries replicated but uncommitted under a dead leader commit
        promptly once the new leader's no-op barrier lands (no client
        write needed)."""
        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            leader.apply(("compact", (0,), {}))
            # partition the leader so its next append replicates nowhere
            transport.partition(leader.id)
            followers = [n for n in nodes.values() if n is not leader]
            new_leader = _wait_leader({n.id: n for n in followers})
            # the new leader commits its barrier without any client write
            deadline = time.time() + 3
            while time.time() < deadline:
                if all(len(applied[f.id]) >= 1 for f in followers):
                    break
                time.sleep(0.02)
            assert new_leader.commit_index >= new_leader.log.last()[0] - 0
            # and a write through the new leader still works
            new_leader.apply(("compact", (1,), {}))
            assert any(c[1] == (1,) for c in applied[new_leader.id])
        finally:
            for n in nodes.values():
                n.stop()

    def test_proposer_stamps_timestamps(self):
        """Timestamped mutations must carry the proposer's clock inside the
        replicated command, so a replica replaying the log later applies
        identical modify_times (ADVICE: GC-cutoff divergence)."""
        from nomad_tpu.raft.fsm import RaftStore, TIMESTAMPED
        from nomad_tpu.state.store import StateStore

        captured = {}

        class FakeRaft:
            def apply(self, cmd):
                captured["cmd"] = cmd
                return 1

        rs = RaftStore(StateStore(), FakeRaft())
        a = mock.alloc()
        rs.upsert_allocs([a])
        name, args, kwargs = captured["cmd"]
        assert name == "upsert_allocs"
        assert kwargs.get("ts") is not None
        # replay on two stores -> identical stamps
        s1, s2 = StateStore(), StateStore()
        import copy as _copy
        s1.upsert_allocs(_copy.deepcopy(list(args[0])), **kwargs)
        time.sleep(0.01)
        s2.upsert_allocs(_copy.deepcopy(list(args[0])), **kwargs)
        assert (s1.snapshot().alloc_by_id(a.id).modify_time ==
                s2.snapshot().alloc_by_id(a.id).modify_time)
        assert "upsert_plan_results" in TIMESTAMPED


class TestBatchedWritePath:
    """ISSUE 4: group commit, conflict-hint catch-up, and the waiter
    registry that replaced the unbounded `_results` map."""

    def test_concurrent_proposers_each_get_their_own_result(self):
        """8 proposers race the group-commit queue; every apply() must
        return the FSM result for ITS OWN command (the waiter registry's
        identity check), and every command applies exactly once."""
        import threading

        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            results = {}
            res_lock = threading.Lock()

            def propose(start):
                for i in range(start, 200, 8):
                    r = leader.apply(("compact", (i,), {}))
                    with res_lock:
                        results[i] = r

            threads = [threading.Thread(target=propose, args=(k,))
                       for k in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert len(results) == 200
            # the FSM returns the apply-sequence number: all distinct,
            # and results mapped to the right proposal means the i-th
            # command's position in the applied list matches its result
            assert len(set(results.values())) == 200
            mine = [c for c in applied[leader.id] if c[0] == "compact"]
            assert len(mine) == 200  # each applied exactly once
            order = {c[1][0]: pos + 1 for pos, c in
                     enumerate(applied[leader.id])}
            for i, r in results.items():
                assert order[i] == r, \
                    f"proposal {i} got another entry's result"
        finally:
            for n in nodes.values():
                n.stop()

    def test_cluster_of_one_commits_through_the_log_writer(self):
        """A single node has no one to replicate to and still commits
        through the group-commit log writer: one apply() is one flush,
        and a burst queued while the writer cannot drain lands as ONE
        append (one `nomad.raft.fsyncs`), each proposal answered with
        its own FSM result."""
        from nomad_tpu.core.metrics import REGISTRY

        transport, nodes, applied = _mini_cluster(n=1)
        try:
            leader = _wait_leader(nodes)
            before = REGISTRY.get("nomad.raft.fsyncs")
            first = leader.apply(("cmd", (-1,), {}))
            assert REGISTRY.get("nomad.raft.fsyncs") - before == 1
            with leader._lock:      # the writer drains under this lock
                props = [leader.apply_async(("cmd", (i,), {}))
                         for i in range(16)]
            results = [leader.apply_wait(p) for p in props]
            assert REGISTRY.get("nomad.raft.fsyncs") - before == 2
            assert results == list(range(first + 1, first + 17))
            mine = [c[1][0] for c in applied[leader.id] if c[0] == "cmd"]
            assert mine == [-1] + list(range(16))
        finally:
            for n in nodes.values():
                n.stop()

    def test_follower_conflict_hint_shape(self):
        """On a prev-entry mismatch the follower reports the conflicting
        term and its first index, so the leader backtracks a term per
        round trip instead of one index."""
        transport = InProcTransport()
        node = RaftNode("a", ["a", "b"], transport, lambda c: None,
                        election_timeout=999, heartbeat_interval=999)
        for term in (1, 1, 2, 2, 2):
            node.log.append(term, ("noop", (), {}))
        # leader probes past our tail: hint says "start at my tail + 1"
        reply = node.handle({"kind": "append_entries", "term": 3,
                             "leader": "b", "prev_log_index": 9,
                             "prev_log_term": 3, "entries": [],
                             "leader_commit": 0})
        assert not reply["success"]
        assert reply["conflict_term"] == 0 and reply["first_index"] == 6
        # term mismatch at prev: hint names our term-2 run start
        reply = node.handle({"kind": "append_entries", "term": 3,
                             "leader": "b", "prev_log_index": 5,
                             "prev_log_term": 3, "entries": [],
                             "leader_commit": 0})
        assert not reply["success"]
        assert reply["conflict_term"] == 2 and reply["first_index"] == 3

    def test_leader_backtracks_past_conflicting_term(self):
        transport = InProcTransport()
        node = RaftNode("a", ["a", "b"], transport, lambda c: None,
                        election_timeout=999, heartbeat_interval=999)
        for term in (1, 1, 2, 3, 3):
            node.log.append(term, ("noop", (), {}))
        # follower conflicts in term 2 starting at 3; we hold term 2
        # only at index 3 -> resend from 4 (just past our last of term 2)
        nxt = node._conflict_next_index_locked(
            {"conflict_term": 2, "first_index": 3}, next_idx=6)
        assert nxt == 4
        # follower names a term we don't hold at all -> jump to its
        # first_index
        nxt = node._conflict_next_index_locked(
            {"conflict_term": 7, "first_index": 2}, next_idx=6)
        assert nxt == 2
        # hint-less peer (legacy reply) -> decrement-by-one fallback
        assert node._conflict_next_index_locked({}, next_idx=6) == 5

    def test_follower_commit_capped_at_verified_prefix(self):
        """leader_commit must never commit a follower's stale divergent
        tail: the cap is the last entry THIS RPC verified, not the
        follower's own last index."""
        from nomad_tpu.raft.log import Entry

        transport = InProcTransport()
        node = RaftNode("a", ["a", "b"], transport, lambda c: None,
                        election_timeout=999, heartbeat_interval=999)
        # stale tail from a deposed leader: term-1 entries 1..4
        for _ in range(4):
            node.log.append(1, ("compact", (0,), {}))
        # the real leader (term 3) confirms only entry 1 and pushes
        # entry 2; its commit index (4) refers to ITS entries, not ours
        reply = node.handle({
            "kind": "append_entries", "term": 3, "leader": "b",
            "prev_log_index": 1, "prev_log_term": 1,
            "entries": [Entry(index=2, term=3, command=("noop", (), {}))],
            "leader_commit": 4})
        assert reply["success"]
        assert node.commit_index == 2, \
            "commit beyond the verified prefix would apply stale entries"

    def test_timed_out_waiter_unregisters(self):
        """A proposal that times out must leave no waiter behind (the
        pre-batch code leaked `_results` entries when the waiter gave up
        before the result landed)."""
        transport, nodes, applied = _mini_cluster()
        try:
            leader = _wait_leader(nodes)
            leader.apply(("compact", (0,), {}))
            # cut the leader off: proposals append but can never commit
            transport.partition(leader.id)
            with pytest.raises((TimeoutError, NotLeaderError)):
                leader.apply(("compact", (1,), {}), timeout=0.4)
            with leader._lock:
                assert not leader._waiters, "timed-out waiter leaked"
                assert not leader._proposals
        finally:
            for n in nodes.values():
                n.stop()


class TestRaftConfigurationEndpoint:
    def test_single_server_reports_single_mode(self):
        import json
        import urllib.request

        from nomad_tpu.api.http import HTTPAgent
        from nomad_tpu.core import Server, ServerConfig

        srv = Server(ServerConfig(num_workers=0, heartbeat_ttl=3600,
                                  gc_interval=3600))
        with srv, HTTPAgent(srv, port=0) as agent:
            out = json.loads(urllib.request.urlopen(
                f"{agent.address}/v1/operator/raft/configuration",
                timeout=10).read())
            assert out["mode"] == "single"

    def test_replicated_reports_peers_and_leader(self):
        import json
        import urllib.request

        from nomad_tpu.api.http import HTTPAgent
        from nomad_tpu.core.server import ServerConfig
        from nomad_tpu.raft.cluster import RaftCluster

        with RaftCluster(3, config_fn=lambda i: ServerConfig(
                num_workers=0, heartbeat_ttl=3600, gc_interval=3600)) as c:
            leader = c.wait_for_leader(15.0)
            assert leader is not None
            agent = HTTPAgent(leader.server, port=0, writer=leader).start()
            try:
                out = json.loads(urllib.request.urlopen(
                    f"{agent.address}/v1/operator/raft/configuration",
                    timeout=10).read())
                assert out["mode"] == "raft"
                assert out["leader"] == leader.id
                assert len(out["servers"]) == 3
                me = next(s for s in out["servers"] if s["self"])
                assert me["leader"] is True
            finally:
                agent.stop()


class TestReplicatedSchedulerConfig:
    def test_config_survives_leader_failover(self):
        """Operator scheduler-config lives in replicated state
        (reference scheduler_config table): after the leader dies, the
        new leader keeps the operator's settings instead of reverting
        to its boot-time config."""
        import time as _time

        from nomad_tpu.raft.cluster import RaftCluster
        from nomad_tpu.structs import enums
        from nomad_tpu.structs.operator import SchedulerConfiguration

        with RaftCluster(3) as cluster:
            leader = cluster.wait_for_leader()
            assert leader is not None
            assert (leader.server.sched_config.scheduler_algorithm
                    == enums.SCHED_ALG_BINPACK)
            leader.set_scheduler_config(SchedulerConfiguration(
                scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK))
            # kill the leader; a follower takes over
            leader.stop()
            deadline = _time.time() + 20
            new_leader = None
            while _time.time() < deadline:
                new_leader = next(
                    (s for s in cluster.servers.values()
                     if s is not leader and s.is_leader()), None)
                if new_leader is not None:
                    break
                _time.sleep(0.05)
            assert new_leader is not None
            # the replicated config governs the new leader
            deadline = _time.time() + 10
            while _time.time() < deadline:
                if (new_leader.server.sched_config.scheduler_algorithm
                        == enums.SCHED_ALG_TPU_BINPACK):
                    break
                _time.sleep(0.05)
            assert (new_leader.server.sched_config.scheduler_algorithm
                    == enums.SCHED_ALG_TPU_BINPACK)
