"""Evaluation broker (reference nomad/eval_broker.go, 1,117 LoC).

Leader-only in-memory dispatch queue for evaluations:

- one ready queue per scheduler type, priority-ordered FIFO
  (eval_broker.go:53 ready heaps);
- per-job serialization: at most one eval per job is ready/unacked at a
  time, the rest wait in a per-job pending heap and are promoted on ack
  (eval_broker.go:214 enqueueLocked / :599 Ack);
- dequeue hands out a delivery token; ack/nack must present it
  (eval_broker.go:385,599);
- un-acked evals are redelivered after nack_timeout; each delivery
  increments a counter and past delivery_limit the eval lands in the
  "_failed" queue for the leader to reap (eval_broker.go:28,678,728);
- evals with wait_until in the future sit in a delay heap and enter the
  ready queue when due (eval_broker.go:873 delayed evals);
- poison-eval quarantine (nomadload): a job whose evals keep hitting
  the delivery limit round after round gets capped-exponential followup
  delays, and after quarantine_threshold rounds the eval is parked in a
  quarantine list that RELEASES the job's serialization token — a
  poisoned eval can delay its own job but never starve sibling evals of
  the per-job ready slot.
"""

from __future__ import annotations

import copy as _copy
import heapq
import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..analysis.sanitizer import sanitized
from ..obs import RECORDER, TRACER
from ..structs import enums
from ..structs.evaluation import Evaluation
from ..utils import generate_secret_uuid

FAILED_QUEUE = "_failed"
# long enough that a slow eval (first jit compile, wide spread jobs) is
# never redelivered mid-flight — duplicate in-flight evals mean duplicate
# placements (the reference also uses 60s, eval_broker.go)
DEFAULT_NACK_TIMEOUT = 60.0
DEFAULT_DELIVERY_LIMIT = 3
# failed-queue rounds (delivery-limit exhaustions) before a job's eval
# chain is quarantined instead of re-entering the failed queue
DEFAULT_QUARANTINE_THRESHOLD = 3


@sanitized
class EvalBroker:
    def __init__(self, nack_timeout: float = DEFAULT_NACK_TIMEOUT,
                 delivery_limit: int = DEFAULT_DELIVERY_LIMIT,
                 quarantine_threshold: int = DEFAULT_QUARANTINE_THRESHOLD,
                 admission=None):
        self.nack_timeout = nack_timeout
        self.delivery_limit = delivery_limit
        self.quarantine_threshold = quarantine_threshold
        # loadctl.AdmissionController or None; consulted on enqueue
        self.admission = admission

        self._lock = threading.Condition()
        self._enabled = False
        self._seq = itertools.count()

        # sched type -> heap of (-priority, seq, eval_id)
        self._ready: Dict[str, List[Tuple[int, int, str]]] = {}
        self._evals: Dict[str, Evaluation] = {}          # eval id -> eval (ready or unacked)
        self._job_tracked: Dict[Tuple[str, str], str] = {}  # (ns, job) -> ready/unacked eval id
        # (ns, job) -> heap of (-modify_index, seq, eval) waiting their turn
        self._pending: Dict[Tuple[str, str], List[Tuple[int, int, Evaluation]]] = {}
        self._unacked: Dict[str, dict] = {}              # eval id -> {token, deliveries, timer}
        self._delay: List[Tuple[float, int, Evaluation]] = []  # (wait_until, seq, eval)
        self._delivery_counts: Dict[str, int] = {}
        # eval id -> first-enqueue wall time; ack() observes the
        # enqueue→commit latency histogram from it (an eval is acked
        # only after its plan committed)
        self._enqueue_times: Dict[str, float] = {}
        self._failed: List[Evaluation] = []
        self._cancelled: List[Evaluation] = []           # superseded pending evals
        # (ns, job) -> consecutive failed-queue rounds; reset when any
        # normally-delivered eval for the job acks
        self._fail_rounds: Dict[Tuple[str, str], int] = {}
        self._quarantined: List[Evaluation] = []
        self._delay_thread: Optional[threading.Thread] = None
        # incremented on every enable: a delay thread from a previous
        # enable generation exits on its next wakeup even if the broker
        # was re-enabled before it noticed the disable (nomadcheck
        # broker_batch scenario: two live delay threads otherwise)
        self._delay_gen = 0
        self.stats = {"enqueued": 0, "dequeued": 0, "acked": 0, "nacked": 0,
                      "quarantined": 0}

    # -- lifecycle --

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            if enabled and not self._enabled:
                self._enabled = True
                self._delay_gen += 1
                self._delay_thread = threading.Thread(
                    target=self._run_delay, args=(self._delay_gen,),
                    daemon=True, name="broker-delay")
                self._delay_thread.start()
            elif not enabled and self._enabled:
                self._enabled = False
                self._flush_locked()
                self._lock.notify_all()

    def _flush_locked(self) -> None:
        for info in self._unacked.values():
            t = info.get("timer")
            if t is not None:
                t.cancel()
        self._ready.clear()
        self._evals.clear()
        self._job_tracked.clear()
        self._pending.clear()
        self._unacked.clear()
        self._delay.clear()
        self._failed.clear()
        self._cancelled.clear()
        self._enqueue_times.clear()
        self._fail_rounds.clear()
        self._quarantined.clear()

    @property
    def enabled(self) -> bool:
        return self._enabled

    # -- enqueue --

    def unacked_count(self) -> int:
        """Live gauge (reference nomad.broker.total_unacked)."""
        with self._lock:
            return len(self._unacked)

    def enqueue(self, ev: Evaluation) -> None:
        self._admission_check(ev)
        with self._lock:
            if not self._enabled:
                return
            self._enqueue_locked(ev)
            self._lock.notify_all()

    def enqueue_all(self, evals: List[Evaluation]) -> None:
        if evals:
            self._admission_check(evals[0], cost=float(len(evals)))
        with self._lock:
            if not self._enabled:
                return
            for ev in evals:
                self._enqueue_locked(ev)
            self._lock.notify_all()

    def _admission_check(self, ev: Evaluation, cost: float = 1.0) -> None:
        """nomadload consult at the broker boundary. An eval that was
        already committed to the store (modify_index stamped) is NEVER
        dropped here — shedding acked work breaks the load-smoke
        zero-acked-work-loss invariant; those enqueues only charge the
        tier bucket so pressure reflects the volume. An unpersisted eval
        arriving under a tier>=submit request context may still be
        refused with RetryLater (the caller has not acked anything
        yet)."""
        adm = self.admission
        if adm is None:
            return
        from . import loadctl

        tier = loadctl.current_tier(default=loadctl.TIER_NONE)
        if tier < loadctl.TIER_SUBMIT or tier >= loadctl.TIER_NONE:
            return  # liveness/commit work and unbound internal threads
        if getattr(ev, "modify_index", 0):
            adm.try_admit(tier, source="broker", cost=cost)
        else:
            adm.admit(tier, source="broker", cost=cost)

    def _enqueue_locked(self, ev: Evaluation) -> None:
        if ev.id in self._evals or ev.id in self._unacked:
            return
        self.stats["enqueued"] += 1
        now = time.time()
        self._enqueue_times.setdefault(ev.id, now)
        TRACER.event("eval.enqueued", trace=ev.trace(), job=ev.job_id)
        RECORDER.record("broker", "enqueue", eval=ev.id[:8],
                        job=ev.job_id, type=ev.type)
        if ev.wait_until and ev.wait_until > now:
            heapq.heappush(self._delay, (ev.wait_until, next(self._seq), ev))
            self._lock.notify_all()  # delay loop re-sleeps
            return
        key = (ev.namespace, ev.job_id)
        if ev.job_id and key in self._job_tracked:
            # a sibling eval for this job is in flight: park in pending
            # (one ready eval per job, eval_broker.go:214)
            heapq.heappush(self._pending.setdefault(key, []),
                           (-ev.modify_index, next(self._seq), ev))
            return
        if ev.job_id:
            self._job_tracked[key] = ev.id
        self._evals[ev.id] = ev
        queue = FAILED_QUEUE if ev.status == enums.EVAL_STATUS_FAILED else ev.type
        heapq.heappush(self._ready.setdefault(queue, []),
                       (-ev.priority, next(self._seq), ev.id))

    # -- dequeue --

    def dequeue(self, sched_types: List[str], timeout: Optional[float] = None
                ) -> Tuple[Optional[Evaluation], str]:
        """Blocking dequeue across the given queues. -> (eval, token) or
        (None, "") on timeout/disable."""
        deadline = None if timeout is None else time.time() + timeout
        with self._lock:
            while True:
                if not self._enabled:
                    return None, ""
                best = self._best_ready_locked(sched_types)
                if best is not None:
                    return self._deliver_locked(*best)
                remaining = None if deadline is None else deadline - time.time()
                if remaining is not None and remaining <= 0:
                    return None, ""
                self._lock.wait(remaining if remaining is not None else 1.0)

    def dequeue_batch(self, sched_types: List[str], max_batch: int = 8,
                      timeout: Optional[float] = None,
                      ) -> List[Tuple[Evaluation, str]]:
        """Blocking batch dequeue: wait exactly like dequeue() for the
        first ready eval, then drain up to max_batch-1 more that are
        ready RIGHT NOW (never waiting for stragglers — a batch of one
        beats idling). Returns [(eval, token), ...]; [] on timeout or
        disable. Per-member semantics are identical to dequeue():
        per-job serialization still holds (job siblings park in the
        pending heap until ack), each member gets its own delivery
        token and nack timer, and ack/nack stay per-eval — so one
        failing member of a batch redelivers alone."""
        deadline = None if timeout is None else time.time() + timeout
        with self._lock:
            while True:
                if not self._enabled:
                    return []
                out: List[Tuple[Evaluation, str]] = []
                while len(out) < max_batch:
                    best = self._best_ready_locked(sched_types)
                    if best is None:
                        break
                    out.append(self._deliver_locked(*best))
                if out:
                    return out
                remaining = None if deadline is None else deadline - time.time()
                if remaining is not None and remaining <= 0:
                    return []
                self._lock.wait(remaining if remaining is not None else 1.0)

    def _best_ready_locked(self, sched_types: List[str]
                           ) -> Optional[Tuple[str, Tuple[int, int, str]]]:
        """Best (priority, FIFO) ready entry across the given queues."""
        best = None
        for st in sched_types:
            heap = self._ready.get(st)
            while heap and heap[0][2] not in self._evals:
                heapq.heappop(heap)  # stale entry
            if heap and (best is None or heap[0] < best[1]):
                best = (st, heap[0])
        return best

    def _deliver_locked(self, st: str, entry: Tuple[int, int, str]
                        ) -> Tuple[Evaluation, str]:
        """Pop a ready entry, mint its delivery token, arm its nack
        timer."""
        eval_id = entry[2]
        heapq.heappop(self._ready[st])
        ev = self._evals.pop(eval_id)
        token = generate_secret_uuid()
        timer = threading.Timer(self.nack_timeout,
                                self._nack_timeout, (eval_id, token))
        timer.daemon = True
        info = {"token": token, "eval": ev, "timer": timer, "queue": st,
                "deliveries": self._delivery_count(eval_id) + 1}
        self._unacked[eval_id] = info
        timer.start()
        self.stats["dequeued"] += 1
        # retroactive queue-wait span: first-enqueue time -> now (covers
        # redeliveries too, matching the enqueue_to_commit side table)
        t0 = self._enqueue_times.get(eval_id)
        if t0 is not None:
            TRACER.add_span("eval.queued", t0, time.time(),
                            trace=ev.trace(),
                            deliveries=info["deliveries"])
        RECORDER.record("broker", "dequeue", eval=eval_id[:8],
                        deliveries=info["deliveries"])
        return ev, token

    def _delivery_count(self, eval_id: str) -> int:
        return self._delivery_counts.get(eval_id, 0)

    # -- ack / nack --

    def ack(self, eval_id: str, token: str) -> None:
        with self._lock:
            info = self._unacked.get(eval_id)
            if info is None or info["token"] != token:
                raise ValueError(f"token mismatch for eval {eval_id}")
            info["timer"].cancel()
            del self._unacked[eval_id]
            self._delivery_counts.pop(eval_id, None)
            self.stats["acked"] += 1
            t0 = self._enqueue_times.pop(eval_id, None)
            if t0 is not None:
                from .metrics import REGISTRY
                REGISTRY.observe("nomad.eval.enqueue_to_commit",
                                 time.time() - t0)
            ev = info["eval"]
            TRACER.event("eval.ack", trace=ev.trace())
            RECORDER.record("broker", "ack", eval=eval_id[:8])
            key = (ev.namespace, ev.job_id)
            if info.get("queue") != FAILED_QUEUE:
                # a normal delivery acked: the job's eval chain is
                # healthy again, forget its quarantine history (the
                # reaper's ack of a FAILED_QUEUE delivery must NOT
                # reset the count — that ack is bookkeeping, not
                # evidence the poison cleared)
                self._fail_rounds.pop(key, None)
            if self._job_tracked.get(key) == eval_id:
                del self._job_tracked[key]
            self._promote_pending_locked(key)

    def _promote_pending_locked(self, key: Tuple[str, str]) -> None:
        """Promote the *latest* pending eval for the job; older ones
        are superseded -> cancelled (reference eval dedup)."""
        pending = self._pending.pop(key, None)
        if pending:
            _, _, nxt = heapq.heappop(pending)
            for _, _, stale in pending:
                # record the cancellation on a copy — evals are shared
                # with MVCC store snapshots and must not mutate in
                # place; the server reaper persists these
                upd = _copy.copy(stale)
                upd.status = enums.EVAL_STATUS_CANCELLED
                upd.status_description = "cancelled after more recent eval was processed"
                self._cancelled.append(upd)
                self._enqueue_times.pop(stale.id, None)
            self._enqueue_locked(nxt)
            self._lock.notify_all()

    def nack(self, eval_id: str, token: str) -> None:
        with self._lock:
            info = self._unacked.get(eval_id)
            if info is None or info["token"] != token:
                raise ValueError(f"token mismatch for eval {eval_id}")
            info["timer"].cancel()
            del self._unacked[eval_id]
            self.stats["nacked"] += 1
            RECORDER.record("broker", "nack", eval=eval_id[:8],
                            deliveries=info["deliveries"])
            self._redeliver_locked(info)

    def _nack_timeout(self, eval_id: str, token: str) -> None:
        with self._lock:
            info = self._unacked.get(eval_id)
            if info is None or info["token"] != token:
                return
            del self._unacked[eval_id]
            TRACER.event("eval.redelivered", trace=info["eval"].trace(),
                         deliveries=info["deliveries"])
            RECORDER.record("broker", "nack_timeout", eval=eval_id[:8],
                            deliveries=info["deliveries"])
            self._redeliver_locked(info)

    def _redeliver_locked(self, info: dict) -> None:
        ev = info["eval"]
        key = (ev.namespace, ev.job_id)
        if self._job_tracked.get(key) == ev.id:
            del self._job_tracked[key]
        self._delivery_counts[ev.id] = info["deliveries"]
        if info["deliveries"] >= self.delivery_limit:
            rounds = self._fail_rounds.get(key, 0) + 1 if ev.job_id else 1
            if ev.job_id:
                self._fail_rounds[key] = rounds
            if rounds >= self.quarantine_threshold:
                # poison-eval quarantine: the job's eval chain has hit
                # the delivery limit quarantine_threshold rounds in a
                # row. Park it OUTSIDE the failed queue without
                # re-taking _job_tracked, and promote siblings — a
                # poisoned eval must never starve its job's
                # serialization token.
                self.stats["quarantined"] += 1
                from .metrics import REGISTRY
                REGISTRY.incr("nomad.broker.quarantined")
                TRACER.event("eval.quarantined", trace=ev.trace(),
                             rounds=rounds)
                RECORDER.record("broker", "quarantine", eval=ev.id[:8],
                                rounds=rounds)
                self._quarantined.append(ev)
                self._enqueue_times.pop(ev.id, None)
                self._delivery_counts.pop(ev.id, None)
                self._promote_pending_locked(key)
                self._lock.notify_all()
                return
            # too many failed deliveries: route to the failed queue
            # (eval_broker.go:28 failedQueue)
            RECORDER.record("broker", "failed_queue", eval=ev.id[:8],
                            deliveries=info["deliveries"])
            self._evals[ev.id] = ev
            if ev.job_id:
                self._job_tracked[key] = ev.id
            heapq.heappush(self._ready.setdefault(FAILED_QUEUE, []),
                           (-ev.priority, next(self._seq), ev.id))
        else:
            self._enqueue_locked(ev)
        self._lock.notify_all()

    # -- delayed evals --

    def _run_delay(self, gen: int) -> None:
        while True:
            with self._lock:
                if not self._enabled or gen != self._delay_gen:
                    return
                now = time.time()
                while self._delay and self._delay[0][0] <= now:
                    _, _, ev = heapq.heappop(self._delay)
                    # pushed on the delay heap -> released: the 60 s
                    # follow-up wait of a failed evaluation, in its chain
                    pushed = self._enqueue_times.get(ev.id)
                    if pushed is not None:
                        TRACER.add_span("eval.delayed", pushed, now,
                                        trace=ev.trace(),
                                        reason=ev.triggered_by)
                    ev = _copy.copy(ev)  # store snapshots share the original
                    ev.wait_until = 0.0
                    self._enqueue_locked(ev)
                    self._lock.notify_all()
                sleep_for = (self._delay[0][0] - now) if self._delay else 0.2
                self._lock.wait(min(max(sleep_for, 0.01), 0.2))

    # -- quarantine (nomadload poison-eval handling) --

    def followup_delay(self, ev: Evaluation, base: float) -> float:
        """Delay before a delivery-limited eval's follow-up re-runs:
        capped exponential in the job's consecutive failed-queue
        rounds (base, 2*base, 4*base, ... <= 8*base). A flaky eval
        retries quickly; a repeatedly-failing one backs off before the
        quarantine threshold ends the chain."""
        with self._lock:
            rounds = self._fail_rounds.get((ev.namespace, ev.job_id), 1)
        return min(base * 8.0, base * (2.0 ** max(0, rounds - 1)))

    def drain_quarantined(self) -> List[Evaluation]:
        """Quarantined evals for the reaper to mark failed — no
        follow-up is scheduled for these."""
        with self._lock:
            out, self._quarantined = self._quarantined, []
            return out

    def quarantined_count(self) -> int:
        with self._lock:
            return len(self._quarantined)

    def fail_rounds(self, namespace: str, job_id: str) -> int:
        with self._lock:
            return self._fail_rounds.get((namespace, job_id), 0)

    # -- introspection --

    def inflight(self) -> int:
        with self._lock:
            return len(self._unacked)

    def ready_count(self) -> int:
        with self._lock:
            return len(self._evals)

    def pending_count(self) -> int:
        with self._lock:
            return sum(len(h) for h in self._pending.values())

    def delayed_count(self) -> int:
        with self._lock:
            return len(self._delay)

    def wait_for_reaper_work(self, timeout: Optional[float] = None) -> bool:
        """Block until the reaper has something to do: a failed-queue
        eval is ready or cancelled evals await persistence. True = work
        available, False = timeout or broker disabled. Replaces the
        reaper's 100ms busy-poll — every path that creates reaper work
        (delivery-limit redelivery, failed-eval enqueue, ack-time
        cancellation) already notifies this condition, and set_enabled
        (False) wakes waiters so a stopping server joins promptly."""
        deadline = None if timeout is None else time.time() + timeout
        with self._lock:
            while True:
                if not self._enabled:
                    return False
                heap = self._ready.get(FAILED_QUEUE)
                while heap and heap[0][2] not in self._evals:
                    heapq.heappop(heap)  # stale entry
                if heap or self._cancelled or self._quarantined:
                    return True
                remaining = None if deadline is None else deadline - time.time()
                if remaining is not None and remaining <= 0:
                    return False
                self._lock.wait(remaining if remaining is not None else 1.0)

    def failed_evals(self) -> List[Evaluation]:
        """Evals parked in the failed queue (leader reaps these)."""
        with self._lock:
            heap = self._ready.get(FAILED_QUEUE, [])
            return [self._evals[eid] for _, _, eid in heap if eid in self._evals]

    def drain_cancelled(self) -> List[Evaluation]:
        with self._lock:
            out, self._cancelled = self._cancelled, []
            return out
