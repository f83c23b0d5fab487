"""Read-path horizontal scale tests: the store-index waiter table,
the sharded event broker (truncation semantics under churn), and the
read-index/lease follower-read protocol end to end over HTTP.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from nomad_tpu import mock
from nomad_tpu.api.http import HTTPAgent
from nomad_tpu.core.events import EventBroker
from nomad_tpu.raft import RaftCluster, RaftNode
from nomad_tpu.raft.node import NotLeaderError
from nomad_tpu.raft.transport import InProcTransport
from nomad_tpu.state.store import StateStore


def _commit(store, events=()):
    """Drive one store commit (what FSM mutations do internally)."""
    with store._write_lock:
        gen, _ = store._begin()
        store._commit(gen, list(events))
    return gen


class _Payload:
    def __init__(self, i):
        self.id = f"p{i}"


# ---------------------------------------------------------------------------
# waiter table
# ---------------------------------------------------------------------------


class TestWatchTable:
    def test_immediate_when_past(self):
        store = StateStore()
        _commit(store)
        idx, wake_ts = store.watches.wait_min_index(1, timeout=0.1)
        assert idx >= 1
        assert wake_ts is None  # no park happened

    def test_timeout_returns_current(self):
        store = StateStore()
        t0 = time.time()
        idx, wake_ts = store.watches.wait_min_index(99, timeout=0.15)
        assert time.time() - t0 < 2.0
        assert idx == 0 and wake_ts is None
        assert store.watches.parked() == 0  # cancelled lazily but counted out

    def test_commit_wakes_parked(self):
        store = StateStore()
        out = {}

        def park():
            out["res"] = store.watches.wait_min_index(1, timeout=5.0)

        t = threading.Thread(target=park)
        t.start()
        deadline = time.time() + 2.0
        while store.watches.parked() < 1 and time.time() < deadline:
            time.sleep(0.005)
        assert store.watches.parked() == 1
        _commit(store)
        t.join(2.0)
        idx, wake_ts = out["res"]
        assert idx == 1
        assert wake_ts is not None and wake_ts <= time.time()
        assert store.watches.parked() == 0

    def test_selective_wakeup(self):
        """A commit at N wakes only waiters with threshold <= N."""
        store = StateStore()
        results = {}

        def park(name, want):
            results[name] = store.watches.wait_min_index(want, timeout=5.0)

        near = threading.Thread(target=park, args=("near", 1))
        far = threading.Thread(target=park, args=("far", 3))
        near.start()
        far.start()
        deadline = time.time() + 2.0
        while store.watches.parked() < 2 and time.time() < deadline:
            time.sleep(0.005)
        _commit(store)
        near.join(2.0)
        assert results["near"][0] == 1
        assert "far" not in results  # still parked
        assert store.watches.parked() == 1
        _commit(store)
        _commit(store)
        far.join(2.0)
        assert results["far"][0] == 3
        assert store.watches.parked() == 0

    def test_many_waiters_one_batch(self):
        store = StateStore()
        n = 64
        done = []
        lock = threading.Lock()

        def park():
            res = store.watches.wait_min_index(1, timeout=5.0)
            with lock:
                done.append(res)

        threads = [threading.Thread(target=park) for _ in range(n)]
        for t in threads:
            t.start()
        deadline = time.time() + 5.0
        while store.watches.parked() < n and time.time() < deadline:
            time.sleep(0.005)
        _commit(store)
        for t in threads:
            t.join(5.0)
        assert len(done) == n
        assert all(idx == 1 for idx, _ in done)


# ---------------------------------------------------------------------------
# sharded event broker
# ---------------------------------------------------------------------------


class TestShardedBroker:
    def test_publish_and_filter(self):
        store = StateStore()
        b = EventBroker(store, ring_size=64)
        sub = b.subscribe({"Node": ["*"]})
        b.publish("Node", "node-upsert", {"node_id": "a"})
        evs = sub.next_events(timeout=1.0)
        assert [e.topic for e in evs] == ["Node"]
        sub.close()

    def test_commit_fanout_all_topics(self):
        store = StateStore()
        b = EventBroker(store, ring_size=64)
        sub = b.subscribe()
        _commit(store, [("node-upsert", _Payload(1)),
                        ("job-upsert", _Payload(2)),
                        ("eval-upsert", _Payload(3))])
        evs = []
        deadline = time.time() + 2.0
        while len(evs) < 3 and time.time() < deadline:
            evs.extend(sub.next_events(timeout=0.2))
        assert sorted(e.type for e in evs) == [
            "eval-upsert", "job-upsert", "node-upsert"]
        # all three carry the commit's store index
        assert len({e.index for e in evs}) == 1
        sub.close()

    def test_truncation_exactly_one_marker(self):
        """Falling off the ring yields ONE truncation marker, then the
        subscriber resyncs cleanly."""
        store = StateStore()
        b = EventBroker(store, ring_size=4)
        sub = b.subscribe({"Node": ["*"]})
        for i in range(20):
            b.publish("Node", "node-upsert", {"node_id": f"n{i}"})
        evs = sub.next_events(timeout=1.0)
        assert sub.truncated
        assert len(evs) == 4  # the ring's worth
        assert evs[-1].key == "n19"  # the newest survives the wrap
        # resync: reset the flag, keep consuming — no second marker
        sub.truncated = False
        b.publish("Node", "node-upsert", {"node_id": "fresh"})
        evs = sub.next_events(timeout=1.0)
        assert len(evs) == 1 and not sub.truncated
        sub.close()

    def test_truncation_across_ring_wrap_live_publisher(self):
        """A subscriber that keeps falling behind a live publisher sees
        a marker per gap but never misses post-resync events and never
        deadlocks — across multiple full ring wraps."""
        store = StateStore()
        b = EventBroker(store, ring_size=8)
        sub = b.subscribe({"Node": ["*"]})
        stop = threading.Event()
        published = [0]

        def pump():
            while not stop.is_set():
                # bursts larger than the ring guarantee wraps between
                # two consumer drains
                for _ in range(16):
                    b.publish("Node", "node-upsert", {"node_id": "x"})
                    published[0] += 1
                time.sleep(0.002)

        t = threading.Thread(target=pump)
        t.start()
        try:
            got = 0
            markers = 0
            deadline = time.time() + 3.0
            while published[0] < 400 and time.time() < deadline:
                evs = sub.next_events(timeout=0.2)
                got += len(evs)
                if sub.truncated:
                    markers += 1
                    sub.truncated = False
                time.sleep(0.01)  # force it to lag the ring
        finally:
            stop.set()
            t.join(2.0)
        # consume the tail quietly, then verify liveness post-wrap
        while sub.next_events(timeout=0.1):
            pass
        b.publish("Node", "node-upsert", {"node_id": "final"})
        evs = sub.next_events(timeout=1.0)
        assert [e.key for e in evs] == ["final"]
        assert got > 0 and markers >= 1
        assert published[0] >= 400

    def test_last_seq_events_after_compat(self):
        store = StateStore()
        b = EventBroker(store, ring_size=64)
        cur = b.last_seq()
        b.publish("Job", "job-upsert", {"node_id": "j"})
        evs, truncated = b.events_after(cur, timeout=1.0)
        assert len(evs) == 1 and not truncated
        # int cursor (legacy callers): 0 = from the start of each ring
        evs, truncated = b.events_after(0, timeout=0.2)
        assert len(evs) == 1 and not truncated

    def test_parked_subscriber_woken_by_publish(self):
        store = StateStore()
        b = EventBroker(store, ring_size=64)
        sub = b.subscribe({"Evaluation": ["*"]})
        got = []

        def wait():
            got.extend(sub.next_events(timeout=5.0))

        t = threading.Thread(target=wait)
        t.start()
        deadline = time.time() + 2.0
        while b.waiter_count() < 1 and time.time() < deadline:
            time.sleep(0.005)
        assert b.waiter_count() >= 1
        b.publish("Evaluation", "eval-upsert", {"node_id": "e"})
        t.join(2.0)
        assert len(got) == 1
        assert b.waiter_count() == 0

    def test_close_unparks(self):
        store = StateStore()
        b = EventBroker(store, ring_size=64)
        sub = b.subscribe()
        t = threading.Thread(target=lambda: sub.next_events(timeout=10.0))
        t.start()
        deadline = time.time() + 2.0
        while b.waiter_count() < 1 and time.time() < deadline:
            time.sleep(0.005)
        sub.close()
        t.join(2.0)
        assert not t.is_alive()
        assert b.waiter_count() == 0


# ---------------------------------------------------------------------------
# raft read index
# ---------------------------------------------------------------------------


class TestReadIndex:
    def test_single_node_leader(self):
        transport = InProcTransport()
        node = RaftNode("a", ["a"], transport, lambda cmd: None,
                        election_timeout=0.15, heartbeat_interval=0.03)
        node.start()
        try:
            deadline = time.time() + 5.0
            while not node.is_leader() and time.time() < deadline:
                time.sleep(0.02)
            assert node.is_leader()
            idx = node.read_index()
            assert idx >= node._term_start_index
            # lease=False also works with no peers (trivial quorum)
            assert node.read_index(lease=False) >= idx
        finally:
            node.stop()
            transport.close()

    def test_follower_raises(self):
        transport = InProcTransport()
        node = RaftNode("a", ["a", "b", "c"], transport, lambda cmd: None,
                        election_timeout=1e6, heartbeat_interval=0.05)
        # never started: stays follower
        with pytest.raises(NotLeaderError):
            node.read_index(timeout=0.2)
        transport.close()

    def test_partitioned_leader_cannot_confirm(self):
        """A leader cut off from its peers: once the lease expires, a
        read must fail rather than serve possibly-stale data."""
        transport, nodes = InProcTransport(), {}
        ids = ["a", "b", "c"]
        for nid in ids:
            nodes[nid] = RaftNode(nid, ids, transport, lambda cmd: None,
                                  election_timeout=0.15,
                                  heartbeat_interval=0.03,
                                  lease_duration=0.1)
        for n in nodes.values():
            n.start()
        try:
            deadline = time.time() + 5.0
            leader = None
            while leader is None and time.time() < deadline:
                leaders = [n for n in nodes.values() if n.is_leader()]
                leader = leaders[0] if leaders else None
                time.sleep(0.02)
            assert leader is not None
            assert leader.read_index(timeout=2.0) >= 1
            transport.partition(leader.id)
            time.sleep(0.3)  # let the lease lapse
            with pytest.raises(NotLeaderError):
                # lease invalid -> confirm round -> no quorum answers
                leader.read_index(timeout=1.0)
        finally:
            for n in nodes.values():
                n.stop()
            transport.close()

    def test_cluster_follower_read(self):
        with RaftCluster(3) as cluster:
            leader = cluster.wait_for_leader()
            assert leader is not None
            follower = cluster.followers()[0]
            leader.register_node(mock.node())
            # the write needs a quorum, not this follower: on a loaded
            # host it may not have heard from the new leader yet
            deadline = time.time() + 5.0
            while not follower.known_leader() and time.time() < deadline:
                time.sleep(0.02)
            idx = follower.read_index()
            follower.wait_applied(idx, timeout=5.0)
            snap = follower.store.snapshot()
            assert len(list(snap.nodes())) == 1
            assert follower.known_leader()
            assert leader.last_contact() == 0.0
            assert 0 <= follower.last_contact() < 5.0


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


class TestHTTPReadPath:
    def _get(self, addr, path, timeout=10):
        r = urllib.request.urlopen(f"{addr}{path}", timeout=timeout)
        return json.loads(r.read()), r.headers

    def test_follower_serves_with_headers(self):
        with RaftCluster(3) as cluster:
            leader = cluster.wait_for_leader()
            follower = cluster.followers()[0]
            la = HTTPAgent(leader.server, port=0, writer=leader).start()
            fa = HTTPAgent(follower.server, port=0, writer=follower).start()
            try:
                leader.register_node(mock.node())
                nodes, hdrs = self._get(fa.address, "/v1/nodes")
                assert len(nodes) == 1
                assert hdrs["X-Nomad-KnownLeader"] == "true"
                assert 0 <= int(hdrs["X-Nomad-LastContact"]) < 5000
                # the index is the read snapshot's, not a later one
                assert int(hdrs["X-Nomad-Index"]) >= 1
                _, hdrs = self._get(leader and la.address, "/v1/nodes")
                assert hdrs["X-Nomad-LastContact"] == "0"
                # stale + consistent modes both serve
                nodes, _ = self._get(fa.address, "/v1/nodes?stale=true")
                assert len(nodes) == 1
                nodes, _ = self._get(fa.address, "/v1/nodes?consistent=true")
                assert len(nodes) == 1
            finally:
                la.stop()
                fa.stop()

    def test_blocking_query_wakes_on_commit(self):
        with RaftCluster(3) as cluster:
            leader = cluster.wait_for_leader()
            follower = cluster.followers()[0]
            fa = HTTPAgent(follower.server, port=0, writer=follower).start()
            try:
                leader.register_node(mock.node())
                _, hdrs = self._get(fa.address, "/v1/nodes")
                idx = int(hdrs["X-Nomad-Index"])
                out = {}

                def block():
                    data, h = self._get(
                        fa.address, f"/v1/nodes?index={idx}&wait=10",
                        timeout=20)
                    out["n"] = len(data)
                    out["idx"] = int(h["X-Nomad-Index"])

                t = threading.Thread(target=block)
                t.start()
                deadline = time.time() + 5.0
                while follower.store.watches.parked() < 1 \
                        and time.time() < deadline:
                    time.sleep(0.01)
                assert follower.store.watches.parked() >= 1
                leader.register_node(mock.node())
                t.join(15.0)
                assert out["n"] == 2
                assert out["idx"] > idx
            finally:
                fa.stop()

    def test_wait_accepts_go_durations(self):
        """The reference client sends Go-style waits ("10s", "250ms");
        a bare float() here used to turn them into a 500."""
        from nomad_tpu.api.http import _parse_wait
        from nomad_tpu.core.server import Server, ServerConfig

        assert _parse_wait("10s") == 10.0
        assert _parse_wait("250ms") == 0.25
        assert _parse_wait("1m") == 60.0
        assert _parse_wait("2.5") == 2.5
        assert _parse_wait("") is None
        assert _parse_wait("bogus") is None
        assert _parse_wait("xs") is None

        srv = Server(ServerConfig(num_workers=0, heartbeat_ttl=3600,
                                  gc_interval=3600))
        with srv, HTTPAgent(srv, port=0) as agent:
            srv.register_node(mock.node())
            idx = srv.store.latest_index
            t0 = time.time()
            # nothing commits, so this rides the wait timeout: a
            # duration-style value must park ~150ms, not error
            _, hdrs = self._get(agent.address,
                                f"/v1/nodes?index={idx}&wait=150ms")
            assert 0.1 <= time.time() - t0 < 5.0
            assert int(hdrs["X-Nomad-Index"]) == idx
            # garbage falls back to the default instead of 500ing
            data, _ = self._get(agent.address,
                                f"/v1/nodes?index=0&wait=bogus")
            assert len(data) == 1

    def test_index_header_matches_snapshot(self):
        """Satellite regression: X-Nomad-Index must come from the read
        snapshot, so a payload with N rows never carries index N+k from
        a racing write."""
        from nomad_tpu.core.server import Server, ServerConfig

        srv = Server(ServerConfig(num_workers=0, heartbeat_ttl=3600,
                                  gc_interval=3600))
        with srv, HTTPAgent(srv, port=0) as agent:
            srv.register_node(mock.node())
            snap_index = srv.store.latest_index
            _, hdrs = self._get(agent.address, "/v1/nodes")
            assert int(hdrs["X-Nomad-Index"]) == snap_index
