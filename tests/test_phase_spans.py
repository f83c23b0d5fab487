"""The phase spans inside the two critical sections: `worker.solve`
under the placer's one lock (placer.lock_wait, placer.locked and its six
children; placer.stage before it, outside the lock) and
`plan.commit_round` (store.lock_wait / apply / publish and
a store.listener a commit listener); that they share a clock with the
jax profiler's trace; and the counters and waits added beside them."""

import os
import statistics
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.obs import TRACER
from nomad_tpu.obs.trace import (R_ARGS, R_ID, R_NAME, R_PARENT, R_T0, R_T1,
                                 R_THREAD, R_TRACE)
from nomad_tpu.structs import Spread, enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.testing import Harness

LOCKED_CHILDREN = ["placer.gather", "placer.pack", "placer.ship",
                   "placer.device_wait", "placer.fetch", "placer.register"]
STORE_PHASES = ["store.lock_wait", "store.apply", "store.publish"]


def _tpu_config():
    return SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)


def _racked_node(i: int, racks: int = 4):
    node = mock.node()
    node.meta["rack"] = f"r{i % racks}"
    node.compute_class()
    return node


def _spread_job(count: int = 24):
    """Over HOST_CUTOVER and with a spread: the per-placement scan
    under _PER_EVAL_SOLVE_LOCK, never the count solve."""
    job = mock.job()
    tg = job.task_groups[0]
    tg.count = count
    tg.spreads = [Spread(attribute="${meta.rack}", weight=50)]
    return job


def _children(spans, parent):
    return sorted((r for r in spans if r[R_PARENT] == parent[R_ID]),
                  key=lambda r: r[R_T0])


def _dur(rec) -> float:
    return rec[R_T1] - rec[R_T0]


def _toy_solve(nodes: int = 16, count: int = 24):
    h = Harness()
    h.store.upsert_nodes([_racked_node(i) for i in range(nodes)])
    job = _spread_job(count)
    h.store.upsert_job(job)
    h.process(mock.eval_for(job), sched_config=_tpu_config())
    assert len(h.store.snapshot().allocs_by_job(job.id)) == count


@pytest.fixture
def spread_server():
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
        yield s, TRACER.spans()
    finally:
        s.stop()


@pytest.fixture
def stage_inside_a_hold(monkeypatch):
    """Orders the race: the first holder of _PER_EVAL_SOLVE_LOCK stays
    in its hold (at the in-flight registration) until another worker
    has staged its statics, and no other worker stages before that hold
    is open. A stage that needed the lock would time out here."""
    from nomad_tpu.tensor.overlay import InflightOverlay
    from nomad_tpu.tensor.placer import TPUPlacer

    stage, register = TPUPlacer._stage_statics, InflightOverlay.register
    first_stage, first_hold = threading.Lock(), threading.Lock()
    held, other_staged = threading.Event(), threading.Event()

    def staged(self, *args):
        if first_stage.acquire(blocking=False):
            return stage(self, *args)
        assert held.wait(60.0)
        try:
            return stage(self, *args)
        finally:
            other_staged.set()

    def registered(self, *args):
        if first_hold.acquire(blocking=False):
            held.set()
            assert other_staged.wait(60.0)
        register(self, *args)

    monkeypatch.setattr(TPUPlacer, "_stage_statics", staged)
    monkeypatch.setattr(InflightOverlay, "register", registered)


class TestPlacerPhases:
    def test_stage_precedes_worker_solve_outside_the_lock(
            self, spread_server):
        _, spans = spread_server
        solves = [r for r in spans if r[R_NAME] == "worker.solve"]
        stages = [r for r in spans if r[R_NAME] == "placer.stage"]
        locked = [r for r in spans if r[R_NAME] == "placer.locked"]
        assert len(stages) == len(solves) == len(locked) >= 4
        for solve in solves:
            stage = max((r for r in stages
                         if r[R_THREAD] == solve[R_THREAD]
                         and r[R_T1] <= solve[R_T0]), key=lambda r: r[R_T1])
            # a sibling that comes just before, not a child
            assert stage[R_PARENT] == solve[R_PARENT]
            assert stage[R_TRACE] == solve[R_TRACE]
            assert stage[R_ARGS]["bytes"] > 0
            assert not [r for r in spans
                        if r[R_THREAD] == solve[R_THREAD]
                        and r[R_NAME] in ("worker.solve", "placer.stage")
                        and stage[R_T1] <= r[R_T0] < solve[R_T0]]
            # the hold ships the usage matrix alone, less than the stage
            hold, = [r for r in locked if r[R_PARENT] == solve[R_ID]]
            ship = _children(spans, hold)[2]
            assert 0 < ship[R_ARGS]["bytes"] < stage[R_ARGS]["bytes"]

    def test_a_waiting_workers_stage_overlaps_another_threads_hold(
            self, stage_inside_a_hold, spread_server):
        _, spans = spread_server
        locked = [r for r in spans if r[R_NAME] == "placer.locked"]
        overlaps = [(s, h) for s in spans if s[R_NAME] == "placer.stage"
                    for h in locked
                    if h[R_THREAD] != s[R_THREAD]
                    and h[R_T0] <= s[R_T0] and s[R_T1] <= h[R_T1]]
        assert overlaps
        # and the holds still follow one another
        locked.sort(key=lambda r: r[R_T0])
        for a, b in zip(locked, locked[1:]):
            assert a[R_T1] <= b[R_T0], (a, b)

    def test_no_more_than_three_evaluations_are_staged_or_holding(
            self, spread_server):
        """placer.admit (the wait for one of _SOLVE_ADMIT's three slots)
        comes before every stage, and from its end to the end of
        worker.solve at most three evaluations overlap."""
        _, spans = spread_server
        solves = [r for r in spans if r[R_NAME] == "worker.solve"]
        admits = [r for r in spans if r[R_NAME] == "placer.admit"]
        stages = [r for r in spans if r[R_NAME] == "placer.stage"]
        assert len(admits) == len(stages) == len(solves) >= 4
        edges = []
        for solve in solves:
            admit = max((r for r in admits if r[R_THREAD] == solve[R_THREAD]
                         and r[R_T1] <= solve[R_T0]), key=lambda r: r[R_T1])
            stage, = [r for r in stages if r[R_THREAD] == solve[R_THREAD]
                      and admit[R_T1] <= r[R_T0] and r[R_T1] <= solve[R_T0]]
            assert admit[R_PARENT] == stage[R_PARENT] == solve[R_PARENT]
            edges += [(admit[R_T1], 1), (solve[R_T1], -1)]
        inside = peak = 0
        for _, step in sorted(edges, key=lambda e: (e[0], e[1])):
            inside += step
            peak = max(peak, inside)
        assert 1 <= peak <= 3

    def test_phases_nest_under_worker_solve_and_cover_it(self, spread_server):
        _, spans = spread_server
        solves = [r for r in spans if r[R_NAME] == "worker.solve"]
        assert len(solves) >= 4
        shares = []
        for solve in solves:
            wait, locked = _children(spans, solve)
            assert (wait[R_NAME], locked[R_NAME]) == ("placer.lock_wait",
                                                      "placer.locked")
            assert wait[R_THREAD] == locked[R_THREAD] == solve[R_THREAD]
            assert wait[R_TRACE] == locked[R_TRACE] == solve[R_TRACE]
            shares.append((_dur(wait) + _dur(locked)) / _dur(solve))
            assert solve[R_T0] <= wait[R_T0] and locked[R_T1] <= solve[R_T1]
            args = locked[R_ARGS]
            assert args["k"] == 24 and args["k_pad"] == 32
            assert args["n_pad"] >= 32
            assert 0.0 <= args["cpu_s"] <= _dur(locked) + 0.005
            phases = _children(spans, locked)
            assert [p[R_NAME] for p in phases] == LOCKED_CHILDREN
            # one after the other, inside the parent
            for a, b in zip(phases, phases[1:]):
                assert a[R_T1] <= b[R_T0]
            assert locked[R_T0] <= phases[0][R_T0]
            assert phases[-1][R_T1] <= locked[R_T1]
            ship = phases[2]
            assert ship[R_ARGS]["bytes"] > 0
        # the wait and the hold are all of worker.solve but the release
        # (a racing waiter can take the interpreter lock right there,
        # so the median and not each)
        assert statistics.median(shares) >= 0.9, shares

    def test_the_six_children_cover_the_critical_section(self):
        """What no child covers is the launch window's own bookkeeping,
        a few hundred microseconds whatever the size: under 5% once the
        section is some milliseconds long, as it is at any real size."""
        TRACER.set_enabled(True)
        shares = []
        for run in range(4):               # the first compiles
            TRACER.clear()
            _toy_solve(nodes=2048, count=250)
            spans = TRACER.spans()
            locked, = [r for r in spans if r[R_NAME] == "placer.locked"]
            shares.append(sum(_dur(c) for c in _children(spans, locked))
                          / _dur(locked))
        assert statistics.median(shares[1:]) >= 0.95, shares

    def test_racing_workers_hold_the_lock_one_at_a_time(self, spread_server):
        _, spans = spread_server
        locked = sorted((r for r in spans if r[R_NAME] == "placer.locked"),
                        key=lambda r: r[R_T0])
        assert len(locked) >= 4
        assert len({r[R_THREAD] for r in locked}) >= 2   # it was a race
        for a, b in zip(locked, locked[1:]):
            assert a[R_T1] <= b[R_T0], (a, b)


class TestBlockArmPhases:
    def test_the_block_arm_keeps_the_span_tree_and_counts_every_group(self):
        """A drain of the grid's job (300 fresh placements, a rack
        spread, no port): every staged group is handed over as one
        AllocBlock, under the spans the row loop runs under."""
        TRACER.set_enabled(True)
        TRACER.clear()
        staged = REGISTRY.get("nomad.placer.staged_solves")
        columnar = REGISTRY.get("nomad.placer.columnar_scan_groups")
        s = Server(ServerConfig(num_workers=4, sched_config=_tpu_config()))
        s.start()
        try:
            for i in range(64):
                node = _racked_node(i)
                node.resources.cpu, node.resources.memory_mb = 16000, 65536
                node.compute_class()
                s.register_node(node)
            jobs = [_spread_job(300) for _ in range(4)]
            for job in jobs:
                job.task_groups[0].tasks[0].resources.networks = []
                s.register_job(job)
            assert s.wait_for_idle(120.0)
            snap = s.store.snapshot()
            assert all(len(snap.allocs_by_job(j.id)) == 300 for j in jobs)
            assert sum(b.live_size() for b in snap.alloc_blocks()) == 1200
            spans = TRACER.spans()
        finally:
            s.stop()
        solves = [r for r in spans if r[R_NAME] == "worker.solve"]
        groups = REGISTRY.get("nomad.placer.staged_solves") - staged
        assert groups == len(solves) >= 4
        # (a partly rejected plan's remainder, under BULK_PLACE_MIN, is
        # a staged group of the row loop: rare, and not this arm's)
        assert REGISTRY.get(
            "nomad.placer.columnar_scan_groups") - columnar == len(
                [r for r in solves if r[R_ARGS]["k"] >= 256]) >= 4
        for solve in solves:
            before = sorted(
                (r for r in spans if r[R_PARENT] == solve[R_PARENT]
                 and r[R_THREAD] == solve[R_THREAD]
                 and r[R_T1] <= solve[R_T0]
                 and r[R_NAME] in ("placer.admit", "placer.stage")),
                key=lambda r: r[R_T0])[-2:]
            assert [r[R_NAME] for r in before] == ["placer.admit",
                                                   "placer.stage"]
            wait, locked = _children(spans, solve)
            assert (wait[R_NAME], locked[R_NAME]) == ("placer.lock_wait",
                                                      "placer.locked")
            assert solve[R_ARGS]["k"] == locked[R_ARGS]["k"]
            assert locked[R_ARGS]["k_pad"] >= locked[R_ARGS]["k"]
            assert [p[R_NAME] for p in _children(spans, locked)] \
                == LOCKED_CHILDREN


class TestStorePhases:
    def test_phases_nest_under_commit_round(self, spread_server):
        _, spans = spread_server
        rounds = [r for r in spans if r[R_NAME] == "plan.commit_round"]
        assert rounds
        for rnd in rounds:
            assert 0.0 <= rnd[R_ARGS]["cpu_s"] <= _dur(rnd) + 0.005
            phases = _children(spans, rnd)
            assert [p[R_NAME] for p in phases] == STORE_PHASES
            assert all(p[R_THREAD] == rnd[R_THREAD] for p in phases)
            apply, publish = phases[1], phases[2]
            assert apply[R_ARGS]["payloads"] == rnd[R_ARGS]["n"]
            assert apply[R_ARGS]["blocks"] == 0
            listeners = _children(spans, publish)
            assert listeners and all(
                p[R_NAME] == "store.listener" for p in listeners)
            fns = [p[R_ARGS]["fn"] for p in listeners]
            # the watch table is the store's first listener; the event
            # broker publishes every commit
            assert fns[0] == "WatchTable._on_commit"
            assert "EventBroker._on_commit" in fns
        placed = sum(r[R_ARGS]["rows"] for r in spans
                     if r[R_NAME] == "store.apply")
        assert placed == 4 * 24
        verifies = [r for r in spans if r[R_NAME] == "plan.verify"]
        assert verifies and all("cpu_s" in r[R_ARGS] for r in verifies)

    def test_other_writers_commit_without_spans(self):
        TRACER.set_enabled(True)
        TRACER.clear()
        h = Harness()
        h.store.upsert_node(mock.node())
        h.store.upsert_job(mock.job())
        assert not [r for r in TRACER.spans()
                    if r[R_NAME].startswith("store.")]
        # the per-plan writer is the batch writer with one payload: its
        # spans are roots where no commit round is open (as on the
        # FSM's apply thread)
        job = mock.job()
        h.store.upsert_job(job)
        h.process(mock.eval_for(job), sched_config=_tpu_config())
        names = [r[R_NAME] for r in TRACER.spans()
                 if r[R_NAME] in STORE_PHASES]
        assert names == STORE_PHASES
        assert all(r[R_PARENT] == 0 for r in TRACER.spans()
                   if r[R_NAME] in STORE_PHASES)


def _host_events(path: str) -> dict:
    """{name: [(start s, end s)]} of the trace's host plane, on the
    trace's own clock."""
    import jax

    out: dict = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    out.setdefault(e.name.split("#")[0], []).append(
                        (s, s + e.duration_ns * 1e-9, dict(e.stats)))
    return out


class TestOneClockWithTheProfiler:
    def test_device_spans_land_in_the_profilers_trace(self, tmp_path):
        import jax

        TRACER.set_enabled(True)
        _toy_solve()                      # compile outside the trace
        TRACER.clear()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1        # keeps TraceAnnotations
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("test.clock",
                                              t=repr(time.time())):
                pass
            _toy_solve()
        finally:
            jax.profiler.stop_trace()
        files = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        assert files
        events = _host_events(str(files[-1]))
        (c0, _, stats), = events["test.clock"]
        offset = float(stats["t"]) - c0   # trace seconds -> wall
        records = {r[R_NAME]: r for r in TRACER.spans()}
        for name in (["placer.stage", "placer.lock_wait", "placer.locked"]
                     + LOCKED_CHILDREN):
            (e0, e1, _), = events[name]
            rec = records[name]
            assert abs(e0 + offset - rec[R_T0]) < 0.002, name
            assert abs(e1 + offset - rec[R_T1]) < 0.002, name
        # spans that did not ask stay out of the profiler's file
        assert "worker.solve" in records and "worker.solve" not in events
        assert "store.apply" in records and "store.apply" not in events

    def test_kill_switch_records_no_phase_and_opens_no_annotation(self):
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "import test_phase_spans as t\n"
            "from nomad_tpu.obs import TRACER, trace\n"
            "assert not TRACER.enabled\n"
            "t._toy_solve()\n"
            "assert TRACER.spans() == [] and TRACER.dropped == 0\n"
            "assert trace._ANNOTATION is None\n"
            "print('ok')" % os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c", code],
            env={"NOMAD_TPU_TRACE": "0", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": os.pathsep.join(sys.path)},
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0 and "ok" in out.stdout, out.stderr[-2000:]


class TestSolverServiceSpans:
    def test_dispatch_resync_and_fetch_are_live_spans(self):
        from nomad_tpu.tensor.solver import get_service

        TRACER.set_enabled(True)
        TRACER.clear()
        before = dict(get_service().stats)
        s = Server(ServerConfig(num_workers=2, sched_config=_tpu_config()))
        s.start()
        try:
            for _ in range(16):
                s.register_node(mock.node())
            job = mock.batch_job()
            job.task_groups[0].count = 256     # BULK_MIN: the count solve
            s.register_job(job)
            assert s.wait_for_idle(60.0)
        finally:
            s.stop()
        stats = get_service().stats
        assert stats["launches"] > before["launches"]
        for gone in ("busy_s", "launch_s", "overlap_s"):
            assert gone not in stats
        assert "nomad.solver.overlap_occupancy" not in REGISTRY.dump()
        spans = TRACER.spans()
        by_name: dict = {}
        for r in spans:
            by_name.setdefault(r[R_NAME], []).append(r)
        dispatch = by_name["solver.dispatch"][0]
        fetch = by_name["solver.fetch"][0]
        launch = by_name["solver.launch"][0]
        # the first dispatch rebuilds the usage carry inside itself
        resync = by_name["solver.resync"][0]
        assert resync[R_PARENT] == dispatch[R_ID]
        assert dispatch[R_THREAD] == fetch[R_THREAD] == launch[R_THREAD]
        # solver.launch is built from the same clocks: dispatch start
        # to fetch end
        assert abs(launch[R_T0] - dispatch[R_T0]) < 0.002
        assert abs(launch[R_T1] - fetch[R_T1]) < 0.002


class TestWaitsNobodySpanned:
    def test_host_cutover_groups_are_counted(self):
        before = REGISTRY.get("nomad.placer.host_cutover_groups")
        h = Harness()
        for _ in range(4):
            h.store.upsert_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 3           # at or under HOST_CUTOVER
        h.store.upsert_job(job)
        h.process(mock.eval_for(job), sched_config=_tpu_config())
        assert len(h.store.snapshot().allocs_by_job(job.id)) == 3
        assert REGISTRY.get("nomad.placer.host_cutover_groups") == before + 1

    def test_a_delayed_eval_leaves_a_span_when_released(self):
        from nomad_tpu.core.broker import EvalBroker

        TRACER.set_enabled(True)
        TRACER.clear()
        b = EvalBroker()
        b.set_enabled(True)
        try:
            ev = mock.eval_for(mock.job())
            ev.triggered_by = enums.TRIGGER_FAILED_FOLLOW_UP
            ev.wait_until = time.time() + 0.15
            b.enqueue(ev)
            got, token = b.dequeue([ev.type], timeout=5.0)
            assert got is not None and got.id == ev.id
            b.ack(got.id, token)
        finally:
            b.set_enabled(False)
        delayed, = [r for r in TRACER.spans() if r[R_NAME] == "eval.delayed"]
        assert delayed[R_TRACE] == ev.trace()
        assert delayed[R_ARGS]["reason"] == enums.TRIGGER_FAILED_FOLLOW_UP
        assert 0.1 <= _dur(delayed) < 2.0

    def test_a_nack_timeout_leaves_a_redelivered_event(self):
        from nomad_tpu.core.broker import EvalBroker

        TRACER.set_enabled(True)
        TRACER.clear()
        b = EvalBroker(nack_timeout=0.1)
        b.set_enabled(True)
        try:
            ev = mock.eval_for(mock.job())
            b.enqueue(ev)
            got, _ = b.dequeue([ev.type], timeout=5.0)
            assert got is not None
            again, token = b.dequeue([ev.type], timeout=5.0)  # after expiry
            assert again is not None and again.id == ev.id
            b.ack(again.id, token)
        finally:
            b.set_enabled(False)
        events = [r for r in TRACER.spans()
                  if r[R_NAME] == "eval.redelivered"]
        assert len(events) == 1
        assert events[0][R_TRACE] == ev.trace()
        assert events[0][R_ARGS]["deliveries"] == 1


class TestRowsVerified:
    def test_verified_rows_bound_rejected_rows_on_a_doctored_plan(self):
        from nomad_tpu.core.plan_apply import PlanApplier, PlanQueue
        from nomad_tpu.state import StateStore
        from nomad_tpu.structs.plan import Plan

        store = StateStore()
        small, roomy = mock.node(), mock.node()
        small.resources.cpu = 1000
        small.resources.memory_mb = 1024
        small.compute_class()
        for n in (small, roomy):
            store.upsert_node(n)
        job = mock.job()
        store.upsert_job(job)
        q = PlanQueue()
        q.set_enabled(True)
        applier = PlanApplier(store, q)
        fits = Plan(eval_id="e1", snapshot_index=store.latest_index)
        fits.append_alloc(mock.alloc(job, small, index=0))
        fits.append_alloc(mock.alloc(job, roomy, index=1))
        assert not applier.apply(fits).rejected_nodes
        assert applier.stats["nodes_verified"] == 2
        assert applier.stats["nodes_rejected"] == 0
        # doctored: a row ten times what the small node holds, beside
        # one that fits
        doctored = Plan(eval_id="e2", snapshot_index=0)
        big = mock.alloc(job, small, index=2)
        big.allocated_vec = np.asarray(big.allocated_vec) * 10
        doctored.append_alloc(big)
        doctored.append_alloc(mock.alloc(job, roomy, index=3))
        assert applier.apply(doctored).rejected_nodes == [small.id]
        assert applier.stats["nodes_rejected"] == 1
        assert applier.stats["nodes_verified"] == 4
