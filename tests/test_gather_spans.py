"""What `placer.gather` is made of, and what `worker.schedule` keeps:
the spans that partition the usage gather (`overlay.read`,
`feed.lock_wait`, `feed.drain`, `overlay.fold`), the incremental feed's
`drains` / `events_drained` counters, the tensor build's one drain
inside `worker.tensor_build`, `worker.reconcile` and `placer.block`;
that the kill switch records none of them; and the two benchmark
readers that turn them into per-layer metrics."""

import os
import subprocess
import sys
import threading
import time

import pytest

from benchmark import layers
from nomad_tpu import mock
from nomad_tpu.core.events import EventBroker
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.obs import TRACER
from nomad_tpu.obs.trace import (R_ARGS, R_ID, R_NAME, R_PARENT, R_T0, R_T1,
                                 R_THREAD)
from nomad_tpu.state import StateStore
from nomad_tpu.structs import Spread, enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.structs.resources import NetworkResource
from nomad_tpu.tensor.cluster import ClusterStatic
from nomad_tpu.tensor.incremental import StateTracker
from nomad_tpu.testing import Harness

GATHER_PARTS = ["overlay.read", "feed.lock_wait", "feed.drain",
                "overlay.fold"]


def _tpu_config():
    return SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)


def _racked_node(i: int, cpu: int = 0):
    node = mock.node()
    node.meta["rack"] = f"r{i % 4}"
    if cpu:
        node.resources.cpu, node.resources.memory_mb = cpu, 65536
    node.compute_class()
    return node


def _spread_job(count: int = 24, ports: int = 0):
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    tg.spreads = [Spread(attribute="${meta.rack}", weight=50)]
    tg.tasks[0].resources.networks = ([NetworkResource(
        dynamic_ports=[f"p{i}" for i in range(ports)])] if ports else [])
    return job


def _dur(rec) -> float:
    return rec[R_T1] - rec[R_T0]


def _children(spans, parent):
    return sorted((r for r in spans if r[R_PARENT] == parent[R_ID]),
                  key=lambda r: r[R_T0])


def _named(spans, name):
    return [r for r in spans if r[R_NAME] == name]


@pytest.fixture(scope="module")
def served():
    """Four racing workers drain four spread jobs of 24 placements
    (the per-placement scan) on a server whose store has a feed."""
    TRACER.set_enabled(True)
    TRACER.clear()
    s = Server(ServerConfig(num_workers=4, sched_config=_tpu_config()))
    s.start()
    try:
        for i in range(32):
            s.register_node(_racked_node(i))
        jobs = [_spread_job() for _ in range(4)]
        for job in jobs:
            s.register_job(job)
        assert s.wait_for_idle(60.0)
        snap = s.store.snapshot()
        assert all(len(snap.allocs_by_job(j.id)) == 24 for j in jobs)
        yield TRACER.spans()
    finally:
        s.stop()


@pytest.fixture
def fed():
    """A fresh store with a feed of its own (a private tracker) and
    eight nodes; the tracer on and cleared."""
    TRACER.set_enabled(True)
    TRACER.clear()
    store = StateStore()
    feed = StateTracker().attach(store, EventBroker(store))
    nodes = [_racked_node(i) for i in range(8)]
    for n in nodes:
        store.upsert_node(n)
    return store, feed, nodes, ClusterStatic(nodes)


class TestGatherParts:
    def test_feed_and_overlay_spans_partition_the_gather(self, served):
        gathers = _named(served, "placer.gather")
        assert len(gathers) >= 4
        for gather in gathers:
            parts = _children(served, gather)
            assert [p[R_NAME] for p in parts] == GATHER_PARTS
            assert all(p[R_THREAD] == gather[R_THREAD] for p in parts)
            for a, b in zip(parts, parts[1:]):
                assert a[R_T1] <= b[R_T0]
            assert gather[R_T0] <= parts[0][R_T0]
            assert parts[-1][R_T1] <= gather[R_T1]
            assert sum(_dur(p) for p in parts) <= _dur(gather)
            read, _, drain, fold = parts
            assert read[R_ARGS]["entries"] == fold[R_ARGS]["entries"] >= 0
            assert fold[R_ARGS]["rows"] >= 0
            assert 0 <= fold[R_ARGS]["remapped"] <= fold[R_ARGS]["entries"]
            assert drain[R_ARGS]["events"] >= 0
            assert drain[R_ARGS]["rows"] >= 0

    def test_a_builds_drain_lies_inside_worker_tensor_build(self, served):
        builds = _named(served, "worker.tensor_build")
        assert builds
        for build in builds:
            assert set(build[R_ARGS]) == {"n"}
            drains = [r for r in _children(served, build)
                      if r[R_NAME] == "feed.drain"]
            assert len(drains) == 1
            assert build[R_T0] <= drains[0][R_T0]
        # a worker drains inside a build or a gather, and nowhere else
        # (before the build opens least of all)
        homes = {r[R_ID]: r[R_NAME] for r in builds
                 + _named(served, "placer.gather")}
        for drain in _named(served, "feed.drain"):
            if drain[R_THREAD].startswith("worker-"):
                assert drain[R_PARENT] in homes, drain

    def test_reconcile_nests_under_worker_schedule_with_places(self, served):
        schedules = {r[R_ID]: r for r in _named(served, "worker.schedule")}
        reconciles = _named(served, "worker.reconcile")
        assert len(reconciles) >= 4
        for rec in reconciles:
            parent = schedules[rec[R_PARENT]]
            assert parent[R_THREAD] == rec[R_THREAD]
            assert parent[R_T0] <= rec[R_T0] and rec[R_T1] <= parent[R_T1]
            assert rec[R_ARGS]["places"] >= 0
        # every job's first attempt asks for its 24 placements
        assert sum(r[R_ARGS]["places"] == 24 for r in reconciles) >= 4


class TestFeedDrains:
    @pytest.mark.parametrize("n", [1, 12])
    def test_a_drain_after_a_committed_plan_counts_its_rows(self, fed, n):
        store, feed, nodes, static = fed
        assert feed.base_for(static) is not None      # the first resync
        job = mock.job()
        store.upsert_job(job)
        before = feed.stats()
        TRACER.clear()
        store.upsert_plan_results([mock.alloc(job, nodes[i % 8], index=i)
                                   for i in range(n)])
        assert feed.base_for(static) is not None
        drain, = _named(TRACER.spans(), "feed.drain")
        assert drain[R_ARGS]["events"] == n
        assert drain[R_ARGS]["rows"] >= n
        assert "resync" not in drain[R_ARGS]
        after = feed.stats()
        assert after["drains"] - before["drains"] == 1
        assert after["events_drained"] - before["events_drained"] == n

    def test_a_rebuilt_base_is_marked_resync(self, fed):
        store, feed, nodes, static = fed
        assert feed.base_for(static) is not None
        assert feed.device_used(static) is not None
        first, second = _named(TRACER.spans(), "feed.drain")
        assert first[R_ARGS]["resync"] is True
        assert "resync" not in second[R_ARGS]
        assert feed.stats()["drains"] == 2

    def test_the_lock_wait_is_the_wait_behind_another_drain(self, fed):
        store, feed, nodes, static = fed
        assert feed.base_for(static) is not None
        TRACER.clear()
        feed._lock.acquire()
        reader = threading.Thread(target=feed.base_for, args=(static,))
        reader.start()
        time.sleep(0.05)
        feed._lock.release()
        reader.join(10.0)
        spans = TRACER.spans()
        wait, = _named(spans, "feed.lock_wait")
        drain, = _named(spans, "feed.drain")
        assert _dur(wait) >= 0.04
        assert wait[R_PARENT] == drain[R_PARENT] == 0
        assert wait[R_T1] <= drain[R_T0]

    def test_the_tracker_sums_its_feeds_counters(self, fed):
        store, feed, nodes, static = fed
        tracker = feed.tracker
        other = tracker.attach(StateStore(), EventBroker(StateStore()))
        assert other is not feed
        feed.base_for(static)
        feed.base_for(static)
        got = tracker.stats()
        assert got["feeds"] == 2
        assert got["drains"] == feed.stats()["drains"] == 2
        assert got["events_drained"] == feed.stats()["events_drained"]


def _spans_of_a_drain(ports, count):
    TRACER.set_enabled(True)
    h = Harness()
    h.store.upsert_nodes([_racked_node(i, cpu=16000) for i in range(64)])
    job = _spread_job(count, ports=ports)
    h.store.upsert_job(job)
    TRACER.clear()
    h.process(mock.eval_for(job), sched_config=_tpu_config())
    assert len(h.store.snapshot().allocs_by_job(job.id)) == count
    return TRACER.spans()


class TestBlockSpan:
    @pytest.mark.parametrize("ports,blocks", [(0, 1), (2, 1)])
    def test_placer_block_for_a_block_group_and_not_for_rows(
            self, ports, blocks):
        """300 fresh placements, with ports or without, leave the scan
        as one AllocBlock under `placer.block`; a group under 256 takes
        the row loop and opens none (test_no_placer_block_for_rows)."""
        spans = _spans_of_a_drain(ports, 300)
        found = _named(spans, "placer.block")
        assert len(found) == blocks
        for span in found:
            assert 0 < span[R_ARGS]["nodes"] <= 64
            solve, = _named(spans, "worker.solve")
            assert solve[R_T1] <= span[R_T0]
            assert span[R_PARENT] == solve[R_PARENT]

    def test_no_placer_block_for_rows(self):
        spans = _spans_of_a_drain(2, 255)
        assert not _named(spans, "placer.block")
        assert _named(spans, "placer.rows")


def test_the_kill_switch_records_none_and_the_counters_still_move():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import test_gather_spans as t\n"
        "from nomad_tpu import mock\n"
        "from nomad_tpu.core.events import EventBroker\n"
        "from nomad_tpu.obs import TRACER\n"
        "from nomad_tpu.tensor.incremental import StateTracker\n"
        "from nomad_tpu.testing import Harness\n"
        "assert not TRACER.enabled\n"
        "h = Harness()\n"
        "feed = StateTracker().attach(h.store, EventBroker(h.store))\n"
        "h.store.upsert_nodes([t._racked_node(i) for i in range(16)])\n"
        "job = t._spread_job()\n"
        "h.store.upsert_job(job)\n"
        "h.process(mock.eval_for(job), sched_config=t._tpu_config())\n"
        "assert len(h.store.snapshot().allocs_by_job(job.id)) == 24\n"
        "assert TRACER.spans() == [] and TRACER.dropped == 0\n"
        "assert feed.stats()['drains'] >= 2\n"
        "print('ok')" % os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={"NOMAD_TPU_TRACE": "0", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join(sys.path)},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-2000:]


def _rec(name, rid, parent, t0, t1, **args):
    return (name, None, parent, rid, t0, t1, "t", args)


class TestReaders:
    def test_gather_feed_pct_counts_the_feeds_children_of_gathers(self):
        records = [
            _rec("placer.gather", 1, 0, 0.000, 0.010),
            _rec("overlay.read", 2, 1, 0.000, 0.001),
            _rec("feed.lock_wait", 3, 1, 0.001, 0.002),
            _rec("feed.drain", 4, 1, 0.002, 0.008, events=3, rows=3),
            _rec("overlay.fold", 5, 1, 0.008, 0.009),
            # a tensor build's drain is not the gather's
            _rec("worker.tensor_build", 6, 0, 0.020, 0.040),
            _rec("feed.drain", 7, 6, 0.020, 0.030, events=9, rows=9)]
        got = layers.read_all(["placer.gather_feed_pct"],
                              {"spans": {"records": records}})
        assert got["placer.gather_feed_pct"]["value"] == pytest.approx(70.0)
        # the parent's gather opens no feed span
        assert layers.read_all(["placer.gather_feed_pct"], {"spans": {
            "records": [_rec("placer.gather", 1, 0, 0.0, 0.01)]}}) == {}

    def test_us_per_event_and_nothing_without_an_event(self):
        records = [_rec("feed.drain", 1, 0, 0.0, 0.003, events=150, rows=150),
                   _rec("feed.drain", 2, 0, 0.1, 0.101, events=50, rows=50),
                   _rec("feed.lock_wait", 3, 0, 0.0, 0.5)]
        got = layers.read_all(["feed.us_per_event"],
                              {"spans": {"records": records}})
        assert got["feed.us_per_event"]["value"] == pytest.approx(20.0)
        idle = [_rec("feed.drain", 1, 0, 0.0, 0.004, events=0, rows=0)]
        assert layers.read_all(["feed.us_per_event"],
                               {"spans": {"records": idle}}) == {}
        assert layers.read_all(["feed.us_per_event"], {"spans": {}}) == {}
