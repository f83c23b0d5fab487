"""nomadload tests: admission controller (watermarks, brownout
hysteresis, tier-0 protection, token buckets, ledger), deadline
propagation helpers, RetryLater wire rehydration, broker poison-eval
quarantine + admission, transport ingress bounds, and the HTTP overload
surface (413 / 400 / 429 / 504 / degraded-consistency header).
"""

import contextlib
import http.client
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from nomad_tpu import mock
from nomad_tpu.api import ApiClient, HTTPAgent
from nomad_tpu.api.client import ApiError
from nomad_tpu.core import Server, ServerConfig
from nomad_tpu.core.broker import FAILED_QUEUE, EvalBroker
from nomad_tpu.core.loadctl import (
    TIER_COMMIT,
    TIER_LIVENESS,
    TIER_NONE,
    TIER_READ,
    TIER_SUBMIT,
    AdmissionController,
    RetryLater,
    bind_deadline,
    bind_tier,
    check_expired,
    current_deadline,
    current_tier,
    deadline_expired,
    remaining,
    tier_for_method,
)
from nomad_tpu.core.metrics import REGISTRY
from nomad_tpu.raft.transport import SocketTransport
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs.wire import wire_encode


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> float:
        self.t += s
        return self.t


def controller(clk=None, **kw):
    return AdmissionController(clock=clk or FakeClock(), **kw)


# ---------------------------------------------------------------------------
# AdmissionController: watermarks, floors, tier-0, buckets
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_calm_admits_all_tiers(self):
        adm = controller()
        for tier in (TIER_LIVENESS, TIER_COMMIT, TIER_SUBMIT, TIER_READ):
            assert adm.try_admit(tier) is None
        assert adm.stats["admitted"] == 4
        assert adm.stats["shed"] == 0
        assert all(kind == "admit" for _, _, kind, _ in adm.ledger())

    def test_soft_watermark_sheds_reads_only(self):
        clk = FakeClock()
        adm = controller(clk)
        depth = [0]
        adm.register_queue("q", lambda: depth[0], soft=10, hard=100)
        depth[0] = 10
        clk.advance(1.0)  # past the pressure cache window
        assert adm.shed_floor() == TIER_READ
        # pressure 1, floor read: after = 0.25 * 2 * 1
        after = adm.try_admit(TIER_READ)
        assert after == pytest.approx(0.5)
        for tier in (TIER_LIVENESS, TIER_COMMIT, TIER_SUBMIT):
            assert adm.try_admit(tier) is None

    def test_hard_watermark_sheds_submits_and_reads(self):
        clk = FakeClock()
        adm = controller(clk)
        depth = [0]
        adm.register_queue("q", lambda: depth[0], soft=10, hard=100)
        depth[0] = 100
        clk.advance(1.0)
        assert adm.shed_floor() == TIER_SUBMIT
        # pressure 2: submit waits 0.25*3*1, read waits 0.25*3*2
        assert adm.try_admit(TIER_SUBMIT) == pytest.approx(0.75)
        assert adm.try_admit(TIER_READ) == pytest.approx(1.5)
        assert adm.try_admit(TIER_COMMIT) is None
        assert adm.try_admit(TIER_LIVENESS) is None
        assert adm.snapshot()["pressure"] == 2

    def test_tier0_never_shed_while_alive(self):
        clk = FakeClock()
        adm = controller(clk)
        adm.register_queue("q", lambda: 10 ** 6, soft=1, hard=2)
        clk.advance(1.0)
        for _ in range(50):
            clk.advance(0.01)
            assert adm.try_admit(TIER_LIVENESS, source="heartbeat") is None
        # invariant 10's ledger shape: no tier-0 shed entry while alive
        assert not [e for e in adm.ledger()
                    if e[1] == TIER_LIVENESS and e[2] == "shed"]
        adm.set_alive(False)
        after = adm.try_admit(TIER_LIVENESS, source="heartbeat")
        assert after is not None and after > 0
        with pytest.raises(RetryLater):
            adm.admit(TIER_LIVENESS)

    def test_token_bucket_flattens_bursts(self):
        clk = FakeClock()
        adm = controller(clk, rates={TIER_SUBMIT: 10.0}, burst_s=1.0)
        for _ in range(10):  # burst depth = rate * burst_s
            assert adm.try_admit(TIER_SUBMIT) is None
        after = adm.try_admit(TIER_SUBMIT)
        assert after is not None and 0 < after <= 0.1
        clk.advance(1.0)  # refill
        assert adm.try_admit(TIER_SUBMIT) is None
        # tiers without a configured bucket are unlimited below the floor
        for _ in range(100):
            assert adm.try_admit(TIER_COMMIT) is None

    def test_brownout_hysteresis(self):
        clk = FakeClock()
        adm = controller(clk, brownout_after=1.0, brownout_exit=3.0)
        depth = [0]
        adm.register_queue("commit_q", lambda: depth[0], soft=10, hard=100,
                           commit_path=True)
        depth[0] = 100
        clk.advance(0.01)
        assert not adm.degraded()  # hot, but not sustained yet
        clk.advance(0.5)
        assert not adm.degraded()
        clk.advance(0.6)  # sustained past brownout_after
        assert adm.degraded()
        assert adm.stats["degraded_entries"] == 1
        # degraded pins the shed floor at submit even after the queue
        # itself drains...
        depth[0] = 0
        clk.advance(0.01)
        assert adm.shed_floor() == TIER_SUBMIT
        assert adm.degraded()
        # degraded contract: submits and watch parks refused, plain
        # reads admitted (HTTP downgrades them to stale-local + header)
        assert adm.try_admit(TIER_SUBMIT) is not None
        assert adm.try_admit(TIER_READ, source="watch") is not None
        assert adm.try_admit(TIER_READ, source="http_get") is None
        # ...a pressure blip resets the calm clock (hysteresis)...
        clk.advance(1.0)
        depth[0] = 10
        clk.advance(0.01)
        assert adm.degraded()
        depth[0] = 0
        clk.advance(1.0)
        assert adm.degraded()  # calm only since the blip ended
        # ...and only sustained calm exits
        clk.advance(3.1)
        assert not adm.degraded()
        assert adm.shed_floor() == TIER_NONE
        assert adm.stats["degraded_entries"] == 1  # no flapping

    def test_two_soft_marks_do_not_hard_trip(self):
        clk = FakeClock()
        adm = controller(clk)
        adm.register_queue("a", lambda: 10, soft=10, hard=100)
        adm.register_queue("b", lambda: 10, soft=10, hard=100)
        clk.advance(1.0)
        assert adm.shed_floor() == TIER_READ
        assert adm.snapshot()["pressure"] == 1

    def test_broken_depth_fn_is_ignored(self):
        clk = FakeClock()
        adm = controller(clk)

        def boom():
            raise RuntimeError("depth source died")

        adm.register_queue("bad", boom, soft=1, hard=2)
        clk.advance(1.0)
        assert adm.shed_floor() == TIER_NONE
        assert adm.try_admit(TIER_READ) is None


# ---------------------------------------------------------------------------
# the store's commit path reads the brownout flag without the lock
# ---------------------------------------------------------------------------


def _commit(store):
    """One store commit, as FSM mutations make it."""
    with store._write_lock:
        gen, _ = store._begin()
        store._commit(gen, [])


def _park(store, index):
    """A blocking query parked at `index` on its own thread; returns once
    the waiter is in the table."""
    out = {}
    t = threading.Thread(target=lambda: out.setdefault(
        "res", store.watches.wait_min_index(index, timeout=10.0)))
    t.start()
    deadline = time.monotonic() + 5.0
    while store.watches.parked() < 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert store.watches.parked() == 1
    return t, out


@contextlib.contextmanager
def _lock_held(lock):
    """`lock` held by this thread, as a racing admission decision holds
    the controller's lock or a subsystem holds its depth counter's."""
    lock.acquire()
    try:
        yield
    finally:
        lock.release()


def _commit_returns(store):
    """Commit on another thread and require it to return within a few
    seconds (it must not wait for what this thread holds)."""
    committer = threading.Thread(target=_commit, args=(store,), daemon=True)
    committer.start()
    committer.join(5.0)
    assert not committer.is_alive(), "the commit waited for the lock"


def _brownout(adm):
    depth = [0]
    adm.register_queue("commit_q", lambda: depth[0], soft=10, hard=100,
                       commit_path=True)
    return depth


class TestCommitPathFlag:
    @pytest.mark.parametrize("holder", ["controller", "depth_source"])
    def test_commit_wakes_a_waiter_while_the_admission_lock_is_held(
            self, holder):
        clk = FakeClock()
        store = StateStore()
        adm = controller(clk)
        # a depth source whose counter sits under a Condition's RLock,
        # as the broker's and the plan queue's do
        cond = threading.Condition()

        def depth():
            with cond:
                return 0

        adm.register_queue("q", depth, soft=10, hard=100, lock=cond)
        store.watches.admission = adm
        waiter, out = _park(store, 1)
        clk.advance(0.01)  # the park's evaluation is stale: one is due
        reads0 = REGISTRY.get("nomad.load.commit_flag_reads")
        busy0 = REGISTRY.get("nomad.load.commit_flag_reads_busy")
        with _lock_held(adm._lock if holder == "controller" else cond):
            _commit_returns(store)
            # the waiter woke while another thread still holds the lock
            waiter.join(5.0)
            assert not waiter.is_alive()
        idx, wake_ts = out["res"]
        assert idx == 1 and wake_ts is not None
        assert REGISTRY.get("nomad.load.commit_flag_reads") >= reads0 + 1
        assert (REGISTRY.get("nomad.load.commit_flag_reads_busy")
                >= busy0 + 1)

    def test_a_read_with_every_lock_free_is_not_busy(self):
        adm = controller()
        cond = threading.Condition()
        adm.register_queue("q", lambda: 0, soft=10, hard=100, lock=cond)
        busy0 = REGISTRY.get("nomad.load.commit_flag_reads_busy")
        assert adm.degraded_now() is False
        # a depth source's lock held while the cached pressure is fresh:
        # degraded() would not have evaluated, so would not have waited
        adm.degraded()
        with _lock_held(cond):
            adm.degraded_now()
        assert REGISTRY.get("nomad.load.commit_flag_reads_busy") == busy0
        # the failed probe left the lock as it found it
        assert cond.acquire(blocking=False)
        cond.release()

    def test_a_waiter_claimed_in_brownout_is_coalesced_and_woken(self):
        clk = FakeClock()
        adm = controller(clk, brownout_after=1.0)
        depth = _brownout(adm)
        store = StateStore()
        store.watches.admission = adm
        # parked while calm: a brownout sheds new watch parks
        waiter, out = _park(store, 1)
        depth[0] = 100
        clk.advance(0.01)
        assert not adm.degraded()
        clk.advance(1.1)
        assert adm.degraded()
        coalesced0 = REGISTRY.get("nomad.load.coalesced_wakeups")
        t0 = time.time()
        with _lock_held(adm._lock):
            _commit_returns(store)
            # the flush needs nothing the lock's holder has either
            waiter.join(5.0)
            assert not waiter.is_alive()
        idx, wake_ts = out["res"]
        assert idx == 1
        # held in the coalescing window, then flushed: never lost
        window = store.watches.COALESCE_WINDOW
        assert 0.9 * window <= wake_ts - t0 < window + 2.0
        assert REGISTRY.get("nomad.load.coalesced_wakeups") >= coalesced0 + 1
        store.watches.teardown()

    def test_the_flag_is_the_one_degraded_returns_and_is_not_advanced(self):
        clk = FakeClock()
        adm = controller(clk, brownout_after=1.0, brownout_exit=3.0)
        depth = _brownout(adm)
        depth[0] = 100
        clk.advance(0.01)
        assert adm.degraded() is adm.degraded_now() is False
        clk.advance(1.1)
        # the read alone evaluates nothing: the flag moves only when an
        # admission, a park or degraded() evaluates pressure
        assert adm.degraded_now() is False
        assert adm.degraded() is adm.degraded_now() is True
        depth[0] = 0
        clk.advance(0.01)
        assert adm.degraded() is adm.degraded_now() is True
        clk.advance(3.1)
        assert adm.degraded_now() is True
        assert adm.degraded() is adm.degraded_now() is False


# ---------------------------------------------------------------------------
# RetryLater wire rehydration + tier classification
# ---------------------------------------------------------------------------


class TestRetryLater:
    def test_roundtrip_from_str(self):
        e = RetryLater(TIER_READ, 1.25, reason="watch")
        r = RetryLater(str(e))
        assert (r.tier, r.after, r.reason) == (TIER_READ, 1.25, "watch")

    def test_roundtrip_with_wire_prefix(self):
        # RemoteCallError prepends the type name before _WIRE_ERRORS
        # rehydrates with cls(str(e))
        e = RetryLater(TIER_SUBMIT, 0.75, reason="broker")
        r = RetryLater("RetryLater: " + str(e))
        assert (r.tier, r.after, r.reason) == (TIER_SUBMIT, 0.75, "broker")

    def test_garbage_message_gets_defaults(self):
        r = RetryLater("total nonsense")
        assert (r.tier, r.after, r.reason) == (TIER_SUBMIT, 0.5, "")

    def test_tier_for_method(self):
        assert tier_for_method("heartbeat") == TIER_LIVENESS
        assert tier_for_method("heartbeat_batch") == TIER_LIVENESS
        assert tier_for_method("mark_nodes_down") == TIER_LIVENESS
        assert tier_for_method("update_allocs_from_client") == TIER_COMMIT
        assert tier_for_method("stop_alloc") == TIER_COMMIT
        assert tier_for_method("job_register") == TIER_SUBMIT
        assert tier_for_method("anything_else") == TIER_SUBMIT


# ---------------------------------------------------------------------------
# deadline propagation helpers
# ---------------------------------------------------------------------------


class TestDeadlines:
    def test_bind_and_restore(self):
        assert current_deadline() is None
        dl = time.time() + 5.0
        with bind_deadline(dl):
            assert current_deadline() == dl
            assert 4.0 < remaining() <= 5.0
            assert not deadline_expired()
            with bind_deadline(dl + 1):
                assert current_deadline() == dl + 1
            assert current_deadline() == dl
        assert current_deadline() is None
        assert remaining(default=7.0) == 7.0

    def test_expired(self):
        with bind_deadline(time.time() - 0.1):
            assert deadline_expired()
            assert remaining() < 0

    def test_tier_binding(self):
        assert current_tier() == TIER_COMMIT  # unbound internal work
        assert current_tier(default=TIER_NONE) == TIER_NONE
        with bind_tier(TIER_READ):
            assert current_tier() == TIER_READ
            assert current_tier(default=TIER_NONE) == TIER_READ
        assert current_tier() == TIER_COMMIT

    def test_check_expired(self):
        assert not check_expired(None, "t")
        assert not check_expired(100.0, "t", now=99.0)
        assert check_expired(100.0, "t", now=100.0)
        assert check_expired(100.0, "t", now=101.0)


# ---------------------------------------------------------------------------
# EvalBroker: poison-eval quarantine + admission gate
# ---------------------------------------------------------------------------


def _fail_one_round(b, ev):
    """Drive one eval through the delivery limit into the failed
    queue, then ack the failed-queue delivery the way the reaper does."""
    b.enqueue(ev)
    for _ in range(b.delivery_limit):
        got, tok = b.dequeue([ev.type], timeout=1.0)
        assert got is not None and got.id == ev.id
        b.nack(got.id, tok)
    got, tok = b.dequeue([FAILED_QUEUE], timeout=1.0)
    assert got.id == ev.id
    b.ack(got.id, tok)


class TestQuarantine:
    def test_quarantined_after_threshold_rounds(self):
        b = EvalBroker(delivery_limit=1, quarantine_threshold=2)
        b.set_enabled(True)
        j = mock.job()
        _fail_one_round(b, mock.eval_for(j))
        assert b.fail_rounds("default", j.id) == 1
        assert b.quarantined_count() == 0
        # round 2 quarantines instead of re-entering the failed queue
        e2 = mock.eval_for(j)
        b.enqueue(e2)
        got, tok = b.dequeue([e2.type], timeout=1.0)
        b.nack(got.id, tok)
        assert b.quarantined_count() == 1
        assert b.stats["quarantined"] == 1
        got, _ = b.dequeue([FAILED_QUEUE], timeout=0.05)
        assert got is None
        drained = b.drain_quarantined()
        assert [e.id for e in drained] == [e2.id]
        assert b.quarantined_count() == 0

    def test_quarantine_releases_job_serialization_token(self):
        """A poisoned eval must never starve its job: the pending
        sibling is promoted the moment the chain is quarantined."""
        b = EvalBroker(delivery_limit=1, quarantine_threshold=1)
        b.set_enabled(True)
        j = mock.job()
        poison = mock.eval_for(j)
        sibling = mock.eval_for(j)
        sibling.modify_index = 99
        b.enqueue(poison)
        b.enqueue(sibling)  # parked pending behind the poison eval
        got, tok = b.dequeue([poison.type], timeout=1.0)
        assert got.id == poison.id
        b.nack(got.id, tok)  # delivery limit 1 + threshold 1 -> quarantine
        assert b.quarantined_count() == 1
        got2, tok2 = b.dequeue([sibling.type], timeout=1.0)
        assert got2 is not None and got2.id == sibling.id
        b.ack(got2.id, tok2)

    def test_healthy_ack_resets_fail_rounds(self):
        b = EvalBroker(delivery_limit=1, quarantine_threshold=5)
        b.set_enabled(True)
        j = mock.job()
        _fail_one_round(b, mock.eval_for(j))
        assert b.fail_rounds("default", j.id) == 1
        # the reaper's FAILED_QUEUE ack above did NOT reset the count;
        # a normal delivery acked does
        ok = mock.eval_for(j)
        b.enqueue(ok)
        got, tok = b.dequeue([ok.type], timeout=1.0)
        b.ack(got.id, tok)
        assert b.fail_rounds("default", j.id) == 0

    def test_followup_delay_capped_exponential(self):
        b = EvalBroker(delivery_limit=1, quarantine_threshold=10)
        b.set_enabled(True)
        j = mock.job()
        ev = mock.eval_for(j)
        assert b.followup_delay(ev, 2.0) == 2.0  # no history: base
        _fail_one_round(b, mock.eval_for(j))
        assert b.followup_delay(ev, 2.0) == 2.0  # round 1: base
        _fail_one_round(b, mock.eval_for(j))
        assert b.followup_delay(ev, 2.0) == 4.0  # round 2: 2x
        _fail_one_round(b, mock.eval_for(j))
        assert b.followup_delay(ev, 2.0) == 8.0  # round 3: 4x
        for _ in range(4):
            _fail_one_round(b, mock.eval_for(j))
        assert b.followup_delay(ev, 2.0) == 16.0  # capped at 8x

    def test_admission_sheds_unpersisted_enqueues_only(self):
        clk = FakeClock()
        adm = controller(clk)
        adm.register_queue("q", lambda: 10 ** 6, soft=1, hard=2)
        clk.advance(1.0)
        b = EvalBroker(admission=adm)
        b.set_enabled(True)
        j = mock.job()
        fresh = mock.eval_for(j)  # modify_index 0: not yet persisted
        with bind_tier(TIER_SUBMIT):
            with pytest.raises(RetryLater):
                b.enqueue(fresh)
            # a COMMITTED eval (raft already acked it) is never dropped
            # at the broker: losing it would strand acked work
            committed = mock.eval_for(j)
            committed.modify_index = 7
            b.enqueue(committed)
        assert b.ready_count() == 1
        # internal (unbound) enqueues — restores, followups — bypass
        # the gate entirely
        other = mock.eval_for(mock.job())
        b.enqueue(other)
        assert b.ready_count() == 2


# ---------------------------------------------------------------------------
# SocketTransport ingress bounds
# ---------------------------------------------------------------------------


def _call_frame(method, dl=None):
    frame = {"t": "call", "method": method, "args": wire_encode(()),
             "kwargs": wire_encode({})}
    if dl is not None:
        frame["dl"] = dl
    return frame


class TestTransportBounds:
    def test_per_peer_inflight_cap(self):
        tr = SocketTransport("n1", "127.0.0.1:0", {},
                             max_inflight_per_peer=1)
        started, release = threading.Event(), threading.Event()
        seen = []

        def handler(method, args, kwargs):
            seen.append(method)
            if method == "job_register":
                started.set()
                assert release.wait(5.0)
            return "ok"

        tr.register_call_handler(handler)
        tr.register("n1", lambda msg: {"echo": True})
        replies = {}

        def first():
            replies["first"] = tr._dispatch(
                _call_frame("job_register"), peer="10.0.0.1")

        t = threading.Thread(target=first, daemon=True)
        t.start()
        assert started.wait(5.0)
        try:
            # same peer, over the cap: typed RetryLater reply
            r = tr._dispatch(_call_frame("job_evaluate"), peer="10.0.0.1")
            assert r["ok"] is False
            assert r["error_type"] == "RetryLater"
            err = RetryLater(r["error"])
            assert err.after == pytest.approx(0.25)
            assert err.reason == "transport inflight cap"
            assert tr.dropped_frames == 1
            # tier-0 calls are never bounded
            r0 = tr._dispatch(_call_frame("heartbeat"), peer="10.0.0.1")
            assert r0["ok"] is True and "heartbeat" in seen
            # a different peer has its own budget
            r2 = tr._dispatch(_call_frame("job_evaluate"), peer="10.0.0.2")
            assert r2["ok"] is True
            # raft frames (consensus liveness) bypass the cap entirely
            rr = tr._dispatch(
                {"t": "raft", "m": wire_encode({"kind": "ping"})},
                peer="10.0.0.1")
            assert rr["ok"] is True
        finally:
            release.set()
            t.join(5.0)
        assert replies["first"]["ok"] is True
        assert tr._inflight == {}  # slots fully released

    def test_cap_zero_disables_bound(self):
        tr = SocketTransport("n1", "127.0.0.1:0", {},
                             max_inflight_per_peer=0)
        tr.register_call_handler(lambda m, a, k: "ok")
        for _ in range(10):
            assert tr._dispatch(_call_frame("job_evaluate"),
                                peer="p")["ok"] is True
        assert tr.dropped_frames == 0

    def test_expired_frame_dropped_before_dispatch(self):
        tr = SocketTransport("n1", "127.0.0.1:0", {})
        calls = []
        tr.register_call_handler(lambda m, a, k: calls.append(m))
        with pytest.raises(TimeoutError):
            tr._dispatch(_call_frame("job_evaluate", dl=time.time() - 1.0),
                         peer="p")
        assert calls == []
        assert tr._inflight == {}
        # a live deadline rides the frame into the handler's TLS
        got = {}

        def capture(m, a, k):
            got["dl"] = current_deadline()
            got["tier"] = current_tier()
            return "ok"

        tr.register_call_handler(capture)
        dl = time.time() + 30.0
        assert tr._dispatch(_call_frame("job_evaluate", dl=dl),
                            peer="p")["ok"] is True
        assert got["dl"] == dl and got["tier"] == TIER_SUBMIT


# ---------------------------------------------------------------------------
# HTTP overload surface
# ---------------------------------------------------------------------------


@pytest.fixture()
def http_stack():
    server = Server(ServerConfig(heartbeat_ttl=30.0))
    server.start()
    agent = HTTPAgent(server, port=0).start()
    yield server, agent
    agent.stop()
    server.stop()


def _post(address, path, body: bytes, headers=None):
    req = urllib.request.Request(address + path, data=body,
                                 headers=headers or {}, method="POST")
    return urllib.request.urlopen(req, timeout=5)


class TestHTTPOverload:
    def test_body_too_large_413(self, http_stack):
        _, agent = http_stack
        host, port = agent.address[len("http://"):].rsplit(":", 1)
        conn = http.client.HTTPConnection(host, int(port), timeout=5)
        try:
            # announce an oversized body and send none of it: the
            # server must refuse before reading a single body byte
            conn.putrequest("POST", "/v1/jobs")
            conn.putheader("Content-Type", "application/json")
            conn.putheader("Content-Length", str((8 << 20) + 1))
            conn.endheaders()
            resp = conn.getresponse()
            assert resp.status == 413
            assert b"too large" in resp.read()
        finally:
            conn.close()

    def test_malformed_json_400(self, http_stack):
        _, agent = http_stack
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(agent.address, "/v1/jobs", b"{definitely not json",
                  {"Content-Type": "application/json"})
        assert ei.value.code == 400
        assert "malformed JSON" in ei.value.read().decode()

    def test_shed_write_gets_429_with_retry_after(self, http_stack):
        server, agent = http_stack
        depth = [10 ** 6]
        server.loadctl.register_queue("test_q", lambda: depth[0],
                                      soft=1, hard=2)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(agent.address, "/v1/jobs", b"{}",
                      {"Content-Type": "application/json"})
            assert ei.value.code == 429
            after = float(ei.value.headers["Retry-After"])
            assert after > 0
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(agent.address + "/v1/jobs",
                                       timeout=5)
            assert ei.value.code == 429
        finally:
            depth[0] = 0

    def test_client_surfaces_429_within_budget(self, http_stack):
        server, agent = http_stack
        depth = [10 ** 6]
        server.loadctl.register_queue("test_q2", lambda: depth[0],
                                      soft=1, hard=2)
        try:
            api = ApiClient(address=agent.address, timeout=0.3)
            t0 = time.time()
            with pytest.raises(ApiError) as ei:
                api.list_jobs()
            assert ei.value.status == 429
            # the deadline bounds the retry loop: never longer than
            # timeout + one Retry-After clamp floor
            assert time.time() - t0 < 5.0
            assert api.retry_budget.stats["requests"] >= 1
        finally:
            depth[0] = 0

    def test_expired_deadline_504(self, http_stack):
        _, agent = http_stack
        req = urllib.request.Request(
            agent.address + "/v1/jobs",
            headers={"X-Nomad-Deadline": f"{time.time() - 1.0:.6f}"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5)
        assert ei.value.code == 504

    def test_degraded_read_header(self, http_stack):
        server, agent = http_stack

        class _FakeRaft:
            def is_leader(self):
                return True

        class _FakeWriter:
            raft = _FakeRaft()

            def known_leader(self):
                return True

            def last_contact(self):
                return 0.0

        agent.writer = _FakeWriter()
        with server.loadctl._lock:
            server.loadctl._degraded = True
        try:
            resp = urllib.request.urlopen(agent.address + "/v1/jobs",
                                          timeout=5)
            assert resp.headers["X-Nomad-Consistency-Degraded"] == "true"
            # stale reads never did the read-index round: no downgrade
            # header to report
            resp = urllib.request.urlopen(
                agent.address + "/v1/jobs?stale=true", timeout=5)
            assert resp.headers.get("X-Nomad-Consistency-Degraded") is None
        finally:
            with server.loadctl._lock:
                server.loadctl._degraded = False
            agent.writer = None

    def test_tiered_server_endpoint_sheds_submit_not_liveness(
            self, http_stack):
        server, _ = http_stack
        depth = [10 ** 6]
        server.loadctl.register_queue("test_q3", lambda: depth[0],
                                      soft=1, hard=2)
        try:
            with pytest.raises(RetryLater):
                server.register_job(mock.job())
            node = mock.node()
            server.register_node(node)  # tier 0: admitted under pressure
            assert server.heartbeat(node.id) > 0
        finally:
            depth[0] = 0
