"""Store-index waiter table: coalesced blocking-query wakeups.

Replaces the HTTP layer's per-watcher 20 ms sleep-poll (the old
``api/http.py:_block`` loop) with a min-heap of parked waiters keyed by
the store index they wait for. One commit publishes ONE timestamped
notification batch: the heap pop wakes exactly the waiters whose index
threshold passed — no per-watcher thread, no poll loop, no latency
floor, and no thundering herd (a waiter parked at index N+100 never
wakes for the commit at N+1).

Deadlines need no timer thread: each parked HTTP handler already owns a
thread, so it enforces its own deadline with ``Event.wait(timeout)`` and
marks its heap entry cancelled on the way out (lazy removal — the entry
is discarded the next time a commit pops past it). The commit/deadline
race is settled under the table lock: a waiter that times out re-checks
its event under the lock, so a wakeup that raced the deadline is never
lost (the nomadcheck ``read_index`` scenario drives this interleaving).
"""

from __future__ import annotations

import heapq
import threading
import time
from typing import List, Optional, Tuple


def _registry():
    """Lazy: importing core.metrics at module load would cycle through
    core/__init__ -> server -> state while state is still loading."""
    global _REG
    if _REG is None:
        from ..core.metrics import REGISTRY
        _REG = REGISTRY
    return _REG


_REG = None


class _Waiter:
    __slots__ = ("event", "index", "wake_ts", "cancelled", "claimed")

    def __init__(self):
        self.event = threading.Event()
        self.index = 0        # the committed index that woke us
        self.wake_ts = 0.0    # commit publish timestamp of that batch
        self.cancelled = False
        self.claimed = False  # popped by a commit; wakeup imminent


class WatchTable:
    """Parked blocking queries for one state store, woken by its commit
    listener. Registered at store construction so every replica — leader
    or follower — wakes its own watchers as replication applies commits
    locally (the substrate for follower blocking queries)."""

    # degraded-mode wakeup coalescing window (nomadload): while the
    # admission controller holds the server in brownout, successive
    # commits flush one merged wakeup batch per window instead of one
    # per commit — watch fan-out is the first read-side cost to shed
    COALESCE_WINDOW = 0.05

    def __init__(self, store):
        self._store = store
        self._lock = threading.Lock()
        self._heap: List[Tuple[int, int, _Waiter]] = []
        self._tie = 0       # FIFO within one index threshold
        self._parked = 0    # live (non-cancelled) waiters
        self._gauge_ts = 0.0
        # loadctl.AdmissionController or None (set by the owning server)
        self.admission = None
        self._coalesce_batch: List[_Waiter] = []
        self._coalesce_idx = 0
        self._coalesce_timer: Optional[threading.Timer] = None
        store.add_commit_listener(self._on_commit)

    def _publish_gauge(self, now: Optional[float] = None) -> None:
        """Refresh the parked gauge at most every 50 ms (call under
        _lock). At fan-out scale thousands of parks per second would
        otherwise serialize on the process-global registry lock — the
        gauge is a scrape-rate observable, not an exact live count."""
        if now is None:
            now = time.time()
        if now - self._gauge_ts >= 0.05:
            self._gauge_ts = now
            _registry().set_gauge("nomad.reads.parked", self._parked)

    def parked(self) -> int:
        with self._lock:
            return self._parked

    def teardown(self) -> None:
        """Owning server's stop: cancel the coalescing timer and flush
        any batch it was holding — a waiter claimed by a commit must
        still wake, even through shutdown. (Named `teardown`, not
        `close`: the fsm-determinism call graph is name-keyed, and
        FSM-reachable code closes snapshots — a `close` here would
        drag the wake path into the determinism scope.)"""
        with self._lock:
            timer = self._coalesce_timer
            self._coalesce_timer = None
        if timer is not None:
            timer.cancel()
        self._flush_coalesced()

    def wait_min_index(self, index: int, timeout: Optional[float] = None
                       ) -> Tuple[int, Optional[float]]:
        """Park until the store publishes ``latest_index >= index`` or
        the timeout expires. Returns ``(observed_index, wake_ts)`` where
        wake_ts is the waking commit's publish timestamp (None when the
        store was already past the threshold or the wait timed out) —
        the bench uses it to measure commit-to-wake latency."""
        latest = self._store.latest_index
        if latest >= index:
            return latest, None
        adm = self.admission
        if adm is not None:
            # nomadload: parking a watcher pins a thread + heap entry;
            # under pressure the read tier is the first one shed.
            # Raises RetryLater (HTTP answers 429 + Retry-After).
            from ..core import loadctl

            adm.admit(loadctl.current_tier(default=loadctl.TIER_READ),
                      source="watch")
        w = _Waiter()
        with self._lock:
            # re-check under the table lock: _on_commit takes it too,
            # so a commit publishing between the check above and the
            # push below is guaranteed to pop this entry
            latest = self._store.latest_index
            if latest >= index:
                return latest, None
            self._tie += 1
            heapq.heappush(self._heap, (index, self._tie, w))
            self._parked += 1
            self._publish_gauge()
        if not w.event.wait(timeout):
            with self._lock:
                if not w.event.is_set() and not w.claimed:
                    # deadline won the race: cancel in place (lazy
                    # removal — a later commit pop discards the entry)
                    w.cancelled = True
                    self._parked -= 1
                    self._publish_gauge()
                    return self._store.latest_index, None
            # a commit claimed this waiter under the lock: its wakeup —
            # possibly held in the degraded-mode coalescing window — is
            # imminent, and the parked query is never lost
            w.event.wait(2 * self.COALESCE_WINDOW + 1.0)
        return w.index, w.wake_ts

    def _on_commit(self, index: int, events: list) -> None:
        """One commit -> one timestamped notification batch. Runs on
        the store's commit path (under raft, the apply thread): reads
        the admission controller's brownout flag without its lock
        (`degraded_now`: no pressure evaluation, so the commit never
        waits for an admission decision), then heap pops under the
        table's own lock, which parks and cancels hold for one heap
        operation, and Event.set. Never re-enters the store."""
        adm = self.admission
        degraded = adm is not None and adm.degraded_now()
        batch: List[_Waiter] = []
        with self._lock:
            heap = self._heap
            while heap and heap[0][0] <= index:
                _, _, w = heapq.heappop(heap)
                if w.cancelled:
                    continue
                w.claimed = True
                batch.append(w)
            if batch:
                self._parked -= len(batch)
                self._publish_gauge()
            if degraded and batch:
                # brownout: hold this batch in the coalescing window so
                # a commit storm flushes one merged wakeup per window
                self._coalesce_batch.extend(batch)
                self._coalesce_idx = max(self._coalesce_idx, index)
                if self._coalesce_timer is None:
                    t = threading.Timer(self.COALESCE_WINDOW,
                                        self._flush_coalesced)
                    t.daemon = True
                    self._coalesce_timer = t
                    t.start()
                return
        if not batch:
            return
        self._wake(batch, index)

    def _flush_coalesced(self) -> None:
        with self._lock:
            batch = self._coalesce_batch
            index = self._coalesce_idx
            self._coalesce_batch = []
            self._coalesce_idx = 0
            self._coalesce_timer = None
        if batch:
            self._wake(batch, index)
            _registry().incr("nomad.load.coalesced_wakeups", len(batch))

    def _wake(self, batch: List[_Waiter], index: int) -> None:
        now = time.time()
        for w in batch:
            w.index = index
            w.wake_ts = now
            w.event.set()
        _registry().incr("nomad.reads.wakeups", len(batch))
        _registry().observe("nomad.reads.wakeup_batch", float(len(batch)))
