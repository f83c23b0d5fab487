"""nomadtrace: tracer rings/nesting/kill-switch, flight recorder,
Chrome export + chain reports, the /v1/traces endpoint, and the
metrics-surface guarantees (/v1/metrics prometheus round-trip,
histogram percentile edge cases, Registry.reset under concurrent
writers)."""

import json
import re
import subprocess
import sys
import threading
import time
import urllib.request

from nomad_tpu import mock
from nomad_tpu.api.http import HTTPAgent
from nomad_tpu.core.metrics import Registry
from nomad_tpu.core.server import Server, ServerConfig
from nomad_tpu.obs import TRACER, FlightRecorder, Tracer
from nomad_tpu.obs.export import (EVAL_CHAIN, chain_report, chrome_trace,
                                  phase_breakdown, render_chain,
                                  spans_for_trace, write_chrome_trace)
from nomad_tpu.obs.trace import (R_ARGS, R_NAME, R_PARENT, R_T0, R_T1,
                                 R_TRACE)


def _span(tr, name, **kw):
    with tr.span(name, **kw):
        pass


class TestTracer:
    def test_span_records_and_sorts(self):
        tr = Tracer(enabled=True)
        with tr.span("b"):
            time.sleep(0.001)
        with tr.span("a", k=3):
            pass
        spans = tr.spans()
        assert [s[R_NAME] for s in spans] == ["b", "a"]  # by t0
        assert spans[1][R_ARGS] == {"k": 3}
        assert spans[0][R_T1] >= spans[0][R_T0]

    def test_nesting_parent_and_trace_inheritance(self):
        tr = Tracer(enabled=True)
        with tr.span("outer", trace="ev-1") as outer:
            with tr.span("inner"):
                pass
        outer_rec, inner = tr.spans()  # sorted by t0: outer opened first
        assert inner[R_NAME] == "inner"
        assert inner[R_PARENT] == outer.sid
        assert inner[R_TRACE] == "ev-1"       # inherited
        assert outer_rec[R_PARENT] == 0

    def test_bind_scopes_trace_to_thread(self):
        tr = Tracer(enabled=True)
        with tr.bind("ev-9"):
            _span(tr, "x")
        _span(tr, "y")
        x, y = tr.spans()
        assert x[R_TRACE] == "ev-9"
        assert y[R_TRACE] is None

    def test_explicit_trace_wins_over_bind(self):
        tr = Tracer(enabled=True)
        with tr.bind("bound"):
            _span(tr, "x", trace="explicit")
        assert tr.spans()[0][R_TRACE] == "explicit"

    def test_set_attaches_args_mid_span(self):
        tr = Tracer(enabled=True)
        with tr.span("x") as sp:
            sp.set(result=7)
        assert tr.spans()[0][R_ARGS]["result"] == 7

    def test_ring_bounded(self):
        tr = Tracer(enabled=True, ring_cap=8)
        for i in range(20):
            _span(tr, f"s{i}")
        spans = tr.spans()
        assert len(spans) == 8
        # newest survive
        assert [s[R_NAME] for s in spans] == [f"s{i}" for i in range(12, 20)]

    def test_dropped_counts_a_wrapped_ring(self):
        tr = Tracer(enabled=True, ring_cap=4)
        for _ in range(3):
            _span(tr, "s")
        assert tr.dropped == 0            # not full yet
        for _ in range(7):
            _span(tr, "s")
        assert len(tr.spans()) == 4 and tr.dropped == 6
        other = threading.Thread(
            target=lambda: [_span(tr, "o") for _ in range(5)])
        other.start()
        other.join(timeout=10)
        assert not other.is_alive()
        assert tr.dropped == 7            # summed over the rings
        tr.clear()
        assert tr.dropped == 0

    def test_cpu_seconds_of_a_sleeping_and_a_spinning_span(self):
        tr = Tracer(enabled=True)
        with tr.span("sleeps", cpu=True):
            time.sleep(0.1)
        with tr.span("spins", cpu=True):
            t_end = time.thread_time() + 0.05
            while time.thread_time() < t_end:
                pass
        _span(tr, "plain")
        sleeps, spins, plain = tr.spans()
        # off-CPU time is wall less cpu_s: all of a sleep, none of a spin
        assert sleeps[R_ARGS]["cpu_s"] < 0.02
        assert sleeps[R_T1] - sleeps[R_T0] >= 0.1
        assert 0.05 <= spins[R_ARGS]["cpu_s"] \
            <= spins[R_T1] - spins[R_T0] + 0.005
        assert "cpu_s" not in plain[R_ARGS]

    def test_event_and_add_span(self):
        tr = Tracer(enabled=True)
        tr.event("e", trace="t", job="j1")
        tr.add_span("late", 10.0, 11.5, trace="t", n=2)
        ev, late = sorted(tr.spans(), key=lambda r: r[R_NAME])
        assert ev[R_T0] == ev[R_T1]
        assert late[R_T0] == 10.0 and late[R_T1] == 11.5
        assert late[R_ARGS] == {"n": 2}

    def test_clear_epoch_drops_all_threads(self):
        tr = Tracer(enabled=True)
        _span(tr, "main")
        t = threading.Thread(target=_span, args=(tr, "worker"))
        t.start()
        t.join()
        assert len(tr.spans()) == 2
        tr.clear()
        assert tr.spans() == []
        _span(tr, "after")  # same thread re-registers lazily
        assert [s[R_NAME] for s in tr.spans()] == ["after"]

    def test_disabled_is_noop(self):
        tr = Tracer(enabled=False)
        with tr.span("x") as sp:
            sp.set(a=1)
        with tr.bind("t"):
            _span(tr, "y")
        tr.event("e")
        tr.add_span("z", 0.0, 1.0)
        assert tr.spans() == []

    def test_concurrent_writers_lock_free(self):
        tr = Tracer(enabled=True, ring_cap=256)

        def burn():
            for _ in range(200):
                _span(tr, "w")

        threads = [threading.Thread(target=burn) for _ in range(4)]
        for t in threads:
            t.start()
        for _ in range(20):
            tr.spans()  # concurrent snapshots must never throw
        for t in threads:
            t.join()
        assert len(tr.spans()) == 4 * 200

    def test_kill_switch_env(self):
        out = subprocess.run(
            [sys.executable, "-c",
             "from nomad_tpu.obs import TRACER, RECORDER, NULL_SPAN\n"
             "assert not TRACER.enabled and not RECORDER.enabled\n"
             "assert TRACER.span('x') is NULL_SPAN\n"
             "RECORDER.record('s', 'e')\n"
             "assert RECORDER.events() == []\n"
             "print('ok')"],
            env={"NOMAD_TPU_TRACE": "0", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0 and "ok" in out.stdout, out.stderr


class TestFlightRecorder:
    def test_record_merge_and_dump(self):
        fr = FlightRecorder(enabled=True)
        fr.record("broker", "enqueue", eval="abc", job="j")
        fr.record("raft", "leader", node="n1", term=3)
        evs = fr.events()
        assert [e[1] for e in evs] == ["broker", "raft"]
        assert fr.events("raft")[0][3] == "leader"
        dump = fr.dump_text()
        assert "enqueue" in dump and "term=3" in dump
        fr.clear()
        assert fr.events() == [] and fr.dump_text() == ""

    def test_ring_bounded_per_subsystem(self):
        fr = FlightRecorder(enabled=True, ring_events=4)
        for i in range(10):
            fr.record("s", f"e{i}")
        evs = fr.events("s")
        assert [e[3] for e in evs] == ["e6", "e7", "e8", "e9"]

    def test_disabled_is_noop(self):
        fr = FlightRecorder(enabled=False)
        fr.record("s", "e")
        assert fr.events() == []


def _mk(name, trace, t0, t1, args=None, parent=0, sid=1):
    return (name, trace, parent, sid, t0, t1, "t0", args or {})


class TestExport:
    def test_chrome_trace_shape(self):
        spans = [_mk("a", "ev", 10.0, 10.5, {"k": 1}, sid=5),
                 _mk("b", None, 10.2, 10.3, parent=5, sid=6)]
        doc = chrome_trace(spans)
        assert doc["displayTimeUnit"] == "ms"
        a, b = doc["traceEvents"]
        assert a["ph"] == "X" and a["ts"] == 0.0 and a["dur"] == 0.5e6
        assert a["args"]["trace"] == "ev" and a["args"]["k"] == 1
        assert b["args"]["parent_span"] == 5
        assert chrome_trace([]) == {"traceEvents": []}

    def test_phase_breakdown(self):
        spans = [_mk("a", None, 0.0, 0.1), _mk("a", None, 0.0, 0.3),
                 _mk("instant", None, 1.0, 1.0)]
        b = phase_breakdown(spans)
        assert b["a"]["count"] == 2
        assert abs(b["a"]["max_ms"] - 300.0) < 1e-6
        assert "instant" not in b  # zero-duration events skipped

    def test_spans_for_trace_includes_batch_spans(self):
        spans = [_mk("mine", "ev-1", 0.0, 1.0),
                 _mk("batch", None, 0.5, 0.6,
                     {"traces": ["ev-1", "ev-2"]}),
                 _mk("other", "ev-2", 0.0, 1.0)]
        got = {s[R_NAME] for s in spans_for_trace(spans, "ev-1")}
        assert got == {"mine", "batch"}

    def test_chain_report_gaps_and_attribution(self):
        spans = [_mk("eval.queued", "ev", 0.0, 1.0, sid=1),
                 _mk("worker.schedule", "ev", 2.0, 3.0, sid=2),
                 _mk("raft.fsync", None, 1.2, 1.8, sid=3)]
        rep = chain_report(spans, "ev",
                           required=("eval.queued", "worker.schedule"))
        assert rep["complete"] and rep["missing"] == []
        assert len(rep["gaps"]) == 1
        gap = rep["gaps"][0]
        assert gap["after"] == "eval.queued"
        assert gap["before"] == "worker.schedule"
        assert gap["attributed"] == ["raft.fsync"]
        assert abs(gap["ms"] - 1000.0) < 1e-6
        assert abs(rep["coverage"] - 2.0 / 3.0) < 1e-6
        assert "complete" in render_chain(rep)

    def test_chain_report_missing(self):
        rep = chain_report([_mk("eval.queued", "ev", 0.0, 1.0)], "ev")
        assert not rep["complete"]
        assert set(rep["missing"]) == set(EVAL_CHAIN) - {"eval.queued"}
        assert "MISSING" in render_chain(rep)

    def test_write_chrome_trace(self, tmp_path):
        path = str(tmp_path / "t.json")
        write_chrome_trace(path, [_mk("a", None, 0.0, 0.1)])
        doc = json.load(open(path))
        assert doc["traceEvents"][0]["name"] == "a"
        assert doc["otherData"]["phases"]["a"]["count"] == 1
        assert doc["otherData"]["dropped"] == 0


class TestLiveTracing:
    """One Server round-trip: spans land, chains complete, /v1/traces
    serves them, and the phase histograms reach /v1/metrics."""

    def test_server_emits_complete_chain_and_endpoint(self):
        TRACER.set_enabled(True)
        TRACER.clear()
        s = Server(ServerConfig(num_workers=1))
        s.start()
        agent = HTTPAgent(s, port=0).start()
        try:
            s.register_node(mock.node())
            job = mock.job()
            job.task_groups[0].count = 2
            s.register_job(job)
            assert s.wait_for_idle(15.0)
            spans = TRACER.spans()
            names = {rec[R_NAME] for rec in spans}
            # single-server path: no raft spans, but the whole eval
            # lifecycle chain must be present and complete per eval
            evs = [ev for ev in s.store.snapshot().evals()
                   if ev.job_id == job.id]
            assert evs
            for ev in evs:
                rep = chain_report(spans, ev.trace(), required=EVAL_CHAIN)
                assert rep["complete"], render_chain(rep)
            assert "eval.persist" in names
            with urllib.request.urlopen(
                    f"{agent.address}/v1/traces?limit=50", timeout=5) as r:
                body = json.loads(r.read())
            assert body["enabled"] is True
            assert body["total_spans"] == len(spans)
            assert body["dropped"] == 0
            assert 0 < len(body["trace"]["traceEvents"]) <= 50
            assert body["phases"]["worker.schedule"]["count"] >= 1
            # the span histograms surfaced in /v1/metrics too
            with urllib.request.urlopen(
                    f"{agent.address}/v1/metrics", timeout=5) as r:
                m = json.loads(r.read())
            assert m["nomad.eval.phase.worker.schedule"]["count"] >= 1
        finally:
            agent.stop()
            s.stop()


PROM_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


class TestMetricsSurface:
    def test_prometheus_round_trip(self):
        s = Server(ServerConfig(num_workers=1))
        s.start()
        agent = HTTPAgent(s, port=0).start()
        try:
            s.register_node(mock.node())
            job = mock.job()
            s.register_job(job)
            assert s.wait_for_idle(15.0)
            with urllib.request.urlopen(
                    f"{agent.address}/v1/metrics", timeout=5) as r:
                families = json.loads(r.read())
            with urllib.request.urlopen(
                    f"{agent.address}/v1/metrics?format=prometheus",
                    timeout=5) as r:
                text = r.read().decode()
            # parse the exposition back: every sample line is
            # "<identifier> <float>", every identifier is valid
            parsed = {}
            for line in text.strip().splitlines():
                if line.startswith("#"):
                    assert line.startswith("# TYPE ")
                    continue
                name, val = line.rsplit(" ", 1)
                assert PROM_NAME.match(name), name
                parsed[name] = float(val)
            assert parsed

            def flat(prefix, v):
                if isinstance(v, dict):
                    for k, sub in v.items():
                        yield from flat(prefix + [str(k)], sub)
                elif isinstance(v, (int, float)):
                    yield "_".join(prefix)

            # every family in the JSON dump appears in the text form
            for name in flat([], families):
                prom = "".join(c if c.isalnum() or c == "_" else "_"
                               for c in name)
                assert prom in parsed, prom
        finally:
            agent.stop()
            s.stop()

    def test_histogram_percentile_edges(self):
        r = Registry()
        assert r.percentile("missing", 0.99) == 0.0
        r.observe("h", 1.0)
        assert r.percentile("h", 0.0) == 1.0
        assert r.percentile("h", 1.0) == 1.0
        d = r.dump()["h"]
        assert d["count"] == 1 and d["p50_ms"] == 1000.0

    def test_histogram_wrapped_ring_window(self):
        r = Registry()
        # 3000 observations into a 2048 ring: the window holds the most
        # recent 2048 (952..2999); count/total still cover all 3000
        for i in range(3000):
            r.observe("h", float(i))
        d = r.dump()["h"]
        assert d["count"] == 3000
        assert d["max_ms"] == 2999 * 1000.0
        assert r.percentile("h", 0.0) == 952.0
        assert r.percentile("h", 1.0) == 2999.0
        p50 = r.percentile("h", 0.5)
        assert 1960.0 < p50 < 1990.0

    def test_reset_isolated_from_concurrent_writers(self):
        r = Registry()
        stop = threading.Event()
        errors = []

        def write():
            try:
                while not stop.is_set():
                    r.incr("c")
                    r.observe("h", 0.001)
                    r.sample("s", 0.001)
                    r.set_gauge("g", 1.0)
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(e)

        threads = [threading.Thread(target=write) for _ in range(4)]
        for t in threads:
            t.start()
        for _ in range(50):
            r.reset()
            r.dump()
            r.percentile("h", 0.99)
        stop.set()
        for t in threads:
            t.join()
        assert not errors
        d = r.dump()  # post-race dump is coherent
        if "h" in d:
            assert d["h"]["count"] >= 1
            assert d["h"]["p50_ms"] >= 0.0
