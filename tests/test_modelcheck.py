"""nomadcheck (analysis/modelcheck.py + the condvar static rules).

Four contracts:
- each condvar-protocol static rule flags exactly its positive fixture
  and stays quiet on the clean twins;
- the deterministic scheduler replays a seed bit-for-bit: same seed,
  same policy => identical trace AND identical outcome;
- every interleaving bug this PR fixed is REPRODUCED by a pinned-seed
  schedule when the old behavior is monkeypatched back in, and the
  same schedule passes on the fixed code;
- a slow exploration sweep (>=200 seeded schedules per scenario)
  finds no violation, deadlock, livelock, or thread leak.
"""

import heapq
import time as _time
from pathlib import Path

import copy as _copy

import pytest

from nomad_tpu.analysis import run_analysis
from nomad_tpu.analysis import modelcheck as mc

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "fixtures" / "analysis"
POSITIVE = FIXTURES / "positive" / "condvar_bad.py"
NEGATIVE = FIXTURES / "negative" / "condvar_clean.py"

CONDVAR_RULES = (
    "condvar-wait-outside-loop",
    "condvar-notify-unlocked",
    "condvar-lost-signal",
    "condvar-wait-no-shutdown-check",
    "thread-no-shutdown-join",
    "queue-enqueue-no-close-check",
)


# ----------------------------------------------------------------- #
# static prong
# ----------------------------------------------------------------- #

class TestCondvarRules:
    def test_positive_fixture_trips_each_rule_once(self):
        findings = run_analysis(paths=[POSITIVE], root=FIXTURES,
                                rules=list(CONDVAR_RULES))
        by_rule = {}
        for f in findings:
            by_rule.setdefault(f.rule, []).append(f)
        assert set(by_rule) == set(CONDVAR_RULES)
        for rule, fs in sorted(by_rule.items()):
            assert len(fs) == 1, (rule, fs)

    def test_negative_fixture_is_clean(self):
        findings = run_analysis(paths=[NEGATIVE], root=FIXTURES,
                                rules=list(CONDVAR_RULES))
        assert findings == []

    def test_real_tree_carries_no_condvar_findings(self):
        """The repo itself must be clean — every finding the new rules
        surfaced was fixed in-code, not baselined."""
        findings = run_analysis(rules=list(CONDVAR_RULES))
        assert findings == [], [f.key() for f in findings]


# ----------------------------------------------------------------- #
# dynamic prong: determinism + green sweeps
# ----------------------------------------------------------------- #

class TestDeterministicReplay:
    def test_same_seed_same_schedule_same_outcome(self):
        a = mc.run_scenario("broker_batch", seed=11)
        b = mc.run_scenario("broker_batch", seed=11)
        assert a.ok and b.ok
        assert a.trace == b.trace
        assert a.steps == b.steps

    def test_different_seeds_explore_different_schedules(self):
        traces = {tuple(mc.run_scenario("broker_batch", seed=s).trace)
                  for s in range(4)}
        assert len(traces) > 1

    def test_policies_are_independent_dimensions(self):
        r = mc.run_scenario("plan_pipeline", seed=5, policy="pbound")
        assert r.ok
        assert r.policy == "pbound"

    def test_seed_from_env(self, monkeypatch):
        monkeypatch.setenv("NOMAD_TPU_CHECK_SEED", "0x2a")
        assert mc.seed_from_env() == 42
        monkeypatch.setenv("NOMAD_TPU_CHECK_SEED", "bogus")
        assert mc.seed_from_env(default=7) == 7
        monkeypatch.delenv("NOMAD_TPU_CHECK_SEED")
        assert mc.seed_from_env(default=3) == 3


class TestScenariosGreen:
    """A handful of seeds per scenario stays in tier-1; the big sweep
    is the slow-marked test below."""

    @pytest.mark.parametrize("name", sorted(mc.SCENARIOS))
    def test_quick_sweep(self, name):
        results = mc.explore(name, range(5))
        bad = [r.render() for r in results if not r.ok]
        assert not bad, bad

    def test_raft_commit_composes_with_fsfaults(self):
        """One schedule also runs under the chaos disk-fault shim: an
        EIO torn into the leader's durable batch append mid-schedule.
        Invariants must hold even though the poisoned batch fails."""
        r = mc.run_scenario("raft_commit", seed=2, fsfaults=True)
        assert r.ok, r.render()


# ----------------------------------------------------------------- #
# pinned-seed regressions: each bug fixed this PR, reproduced by
# re-introducing the old behavior and replaying one seeded schedule
# ----------------------------------------------------------------- #

def _old_run_delay(self, gen):
    """EvalBroker._run_delay as it was before the generation counter:
    a delay thread parked across a disable->enable flip never noticed
    the disable and ran alongside the new generation's thread."""
    while True:
        with self._lock:
            if not self._enabled:
                return
            now = _time.time()
            while self._delay and self._delay[0][0] <= now:
                _, _, ev = heapq.heappop(self._delay)
                ev = _copy.copy(ev)
                ev.wait_until = 0.0
                self._enqueue_locked(ev)
                self._lock.notify_all()
            sleep_for = (self._delay[0][0] - now) if self._delay else 0.2
            self._lock.wait(min(max(sleep_for, 0.01), 0.2))


class TestPinnedSeedRegressions:
    def test_broker_delay_thread_leak_seed0(self, monkeypatch):
        from nomad_tpu.core.broker import EvalBroker

        monkeypatch.setattr(EvalBroker, "_run_delay", _old_run_delay)
        r = mc.run_scenario("broker_batch", seed=0, policy="random")
        assert not r.ok
        assert "broker-delay" in (r.error or "")
        monkeypatch.undo()
        r = mc.run_scenario("broker_batch", seed=0, policy="random")
        assert r.ok, r.render()

    def test_plan_applier_stranded_future_seed0(self, monkeypatch):
        from concurrent.futures import Future

        from nomad_tpu.core import plan_apply as pa

        def old_stop(self):
            # pre-fix stop(): no stranded-entry drain after the commit
            # thread's exit
            self._stop.set()
            self.queue.set_enabled(False)
            if self._thread is not None:
                self._thread.join(timeout=2.0)
            if self._commit_thread is not None:
                with self._commit_cond:
                    self._commit_cond.notify_all()
                self._commit_thread.join(timeout=5.0)
                self._commit_thread = None
            if self._pool is not None:
                self._pool.shutdown(wait=False)

        def old_submit(self, evals):
            # pre-fix submit: no running-commit-thread guard
            fut = Future()
            entry = pa._CommitEntry(None, None, (), 0, None, fut,
                                    payload={"evals": list(evals)})
            with self._commit_cond:
                self._commit_q.append(entry)
                self._commit_cond.notify()
            return fut

        monkeypatch.setattr(pa.PlanApplier, "stop", old_stop)
        monkeypatch.setattr(pa.PlanApplier, "submit_eval_updates",
                            old_submit)
        r = mc.run_scenario("plan_pipeline", seed=0, policy="random")
        assert not r.ok
        assert "stranded" in (r.error or "")
        monkeypatch.undo()
        r = mc.run_scenario("plan_pipeline", seed=0, policy="random")
        assert r.ok, r.render()

    def test_change_config_slow_stepdown_seed0(self, monkeypatch):
        from nomad_tpu.raft import node as node_mod
        from nomad_tpu.raft.node import (LEADER, ConfigInProgressError,
                                         NotLeaderError)

        def old_change_config(self, servers, timeout=5.0):
            # pre-fix change_config: the wait loop never rechecked
            # leadership, so a step-down mid-change burned the whole
            # timeout before failing
            with self._lock:
                if self.state != LEADER:
                    raise NotLeaderError(self.leader_id)
                if self._config_index > self.commit_index:
                    raise ConfigInProgressError()
                entry = self.log.append(
                    self.current_term, ("config", (dict(servers),), {}))
                self._config_index = entry.index
                self._set_servers_locked(servers)
                index = entry.index
                self._maybe_advance_commit_locked()
                self._repl_cond.notify_all()
            deadline = _time.time() + timeout
            with self._apply_cond:
                while self.commit_index < index:
                    remaining = deadline - _time.time()
                    if remaining <= 0 or self._stop.is_set():
                        raise TimeoutError(
                            f"config change {index} timed out")
                    self._apply_cond.wait(min(remaining, 0.5))

        monkeypatch.setattr(node_mod.RaftNode, "change_config",
                            old_change_config)
        r = mc.run_scenario("raft_stepdown", seed=0, policy="random")
        assert not r.ok
        assert "NotLeaderError" in (r.error or "")
        monkeypatch.undo()
        r = mc.run_scenario("raft_stepdown", seed=0, policy="random")
        assert r.ok, r.render()


# ----------------------------------------------------------------- #
# detector self-tests: deadlock / livelock / leak machinery
# ----------------------------------------------------------------- #

class TestDetectors:
    def _run_inline(self, body, max_steps=5_000):
        name = "_inline_detector_test"
        mc.SCENARIOS[name] = body
        try:
            return mc.run_scenario(name, seed=1, max_steps=max_steps)
        finally:
            del mc.SCENARIOS[name]

    def test_deadlock_detected(self):
        def body(env):
            import threading

            a, b = threading.Lock(), threading.Lock()

            def t1():
                with a:
                    with b:
                        pass

            def t2():
                with b:
                    with a:
                        pass

            th1 = threading.Thread(target=t1, name="t1")
            th2 = threading.Thread(target=t2, name="t2")
            th1.start()
            th2.start()
            th1.join()
            th2.join()

        hit = False
        for seed in range(20):
            def wrapped(env, _b=body):
                _b(env)
            mc.SCENARIOS["_dl"] = wrapped
            try:
                r = mc.run_scenario("_dl", seed=seed)
            finally:
                del mc.SCENARIOS["_dl"]
            if not r.ok:
                assert r.error_type == "DeadlockError", r.render()
                hit = True
                break
        assert hit, "AB/BA deadlock never scheduled in 20 seeds"

    def test_livelock_detected(self):
        def body(env):
            import threading

            lock = threading.Lock()
            while True:          # never blocks, never finishes
                with lock:
                    pass

        r = self._run_inline(body, max_steps=500)
        assert not r.ok
        assert r.error_type == "LivelockError"

    def test_thread_leak_detected(self):
        def body(env):
            import threading

            stop = threading.Event()

            def worker():
                while not stop.wait(0.2):
                    pass

            threading.Thread(target=worker, name="leaky").start()
            # scenario returns without stopping/joining the worker

        r = self._run_inline(body)
        assert not r.ok
        assert r.error_type == "ThreadLeakError"
        assert "leaky" in (r.error or "")


# ----------------------------------------------------------------- #
# the big sweep
# ----------------------------------------------------------------- #

@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(mc.SCENARIOS))
def test_exploration_sweep(name):
    """>=200 distinct seeded schedules per scenario (100 seeds x 2
    policies), zero violations/deadlocks/livelocks/leaks."""
    results = mc.explore(name, range(100), stop_on_failure=False)
    assert len(results) >= 200
    bad = [r.render() for r in results if not r.ok]
    assert not bad, bad[:3]


@pytest.mark.slow
def test_fsfaults_sweep():
    results = [mc.run_scenario("raft_commit", s, policy=p, fsfaults=True)
               for s in range(25) for p in ("random", "pbound")]
    bad = [r.render() for r in results if not r.ok]
    assert not bad, bad[:3]
