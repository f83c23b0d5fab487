"""Blocked evaluations tracker (reference nomad/blocked_evals.go, 807 LoC).

Holds evals that couldn't place all their allocations until the cluster
changes in a way that might help: a node update/registration unblocks
evals whose computed-class eligibility doesn't rule the node out (or that
escaped class tracking). One blocked eval per job — a newer one replaces
and cancels the older (blocked_evals.go:37 dedup).
"""

from __future__ import annotations

import copy as _copy
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from ..structs import enums
from ..structs.evaluation import Evaluation


class BlockedEvals:
    def __init__(self, enqueue_fn: Callable[[Evaluation], None],
                 persist_fn: Optional[Callable[[List[Evaluation]], None]] = None):
        """enqueue_fn re-queues an unblocked eval into the broker;
        persist_fn commits eval-status transitions (cancellations) to the
        state store."""
        self._enqueue = enqueue_fn
        self._persist = persist_fn
        self._lock = threading.Lock()
        self._enabled = False
        # (ns, job_id) -> blocked eval
        self._by_job: Dict[Tuple[str, str], Evaluation] = {}
        # evals that escaped class tracking: unblock on any node change
        self._escaped: Dict[str, Evaluation] = {}
        # class -> {eval_id} potentially unblocked by that class
        self._captured: Dict[str, Evaluation] = {}
        # the newest store index at which capacity was freed or a node
        # changed (unblock): an eval whose scheduler ran on an older
        # snapshot missed it and must not wait for the next one
        # (reference blocked_evals.go missedUnblock)
        self._unblock_index = 0
        self.stats = {"blocked": 0, "unblocked": 0, "cancelled": 0,
                      "missed_unblocks": 0}

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self._enabled = enabled
            if not enabled:
                self._by_job.clear()
                self._escaped.clear()
                self._captured.clear()

    def block(self, ev: Evaluation) -> None:
        with self._lock:
            if not self._enabled:
                return
            missed = (ev.snapshot_index
                      and ev.snapshot_index < self._unblock_index)
            if missed:
                self.stats["missed_unblocks"] += 1
        if missed:
            # the cluster changed after the scheduler's snapshot: retry
            # now rather than wait for a change that already happened
            self._enqueue(ev)
            return
        with self._lock:
            if not self._enabled:
                return
            key = (ev.namespace, ev.job_id)
            prev = self._by_job.get(key)
            cancelled = None
            if prev is not None:
                if prev.id == ev.id:
                    return
                # newer blocked eval supersedes: cancel the old one on a
                # copy (the object is shared with store snapshots) and
                # persist the transition
                cancelled = _copy.copy(prev)
                cancelled.status = enums.EVAL_STATUS_CANCELLED
                cancelled.status_description = "superseded by newer blocked eval"
                self._escaped.pop(prev.id, None)
                self._captured.pop(prev.id, None)
                self.stats["cancelled"] += 1
            self._by_job[key] = ev
            if ev.escaped_computed_class or not ev.class_eligibility:
                self._escaped[ev.id] = ev
            else:
                self._captured[ev.id] = ev
            self.stats["blocked"] += 1
        if cancelled is not None and self._persist is not None:
            self._persist([cancelled])

    def untrack_job(self, namespace: str, job_id: str) -> None:
        with self._lock:
            ev = self._by_job.pop((namespace, job_id), None)
            if ev is not None:
                self._escaped.pop(ev.id, None)
                self._captured.pop(ev.id, None)

    def unblock(self, computed_class: str = "", quota: str = "",
                index: int = 0) -> int:
        """A node changed (or quota raised, or capacity was freed at
        store `index`): release candidate evals back to the broker
        (blocked_evals.go Unblock)."""
        with self._lock:
            if not self._enabled:
                return 0
            self._unblock_index = max(self._unblock_index, index)
            release: List[Evaluation] = list(self._escaped.values())
            for ev in list(self._captured.values()):
                elig = ev.class_eligibility.get(computed_class)
                if elig is None or elig:
                    # unknown class for this eval, or known-eligible:
                    # worth retrying
                    release.append(ev)
            for ev in release:
                key = (ev.namespace, ev.job_id)
                self._by_job.pop(key, None)
                self._escaped.pop(ev.id, None)
                self._captured.pop(ev.id, None)
            self.stats["unblocked"] += len(release)
        for ev in release:
            # the callback owns persisting + requeueing (it must not
            # mutate `ev` in place: store snapshots share the object)
            self._enqueue(ev)
        return len(release)

    def unblock_all(self) -> int:
        return self.unblock(computed_class="")

    def unblock_failed(self) -> int:
        """Release evals blocked by plan-attempt exhaustion (optimistic-
        concurrency livelock, not capacity): the conflict storm they lost
        is over shortly after it started, so the leader retries them on a
        timer (reference blocked_evals.go UnblockFailed, driven by
        leader.go:443 periodicUnblockFailedEvals)."""
        with self._lock:
            if not self._enabled:
                return 0
            release = [ev for ev in self._by_job.values()
                       if ev.triggered_by == enums.TRIGGER_MAX_PLANS]
            for ev in release:
                self._by_job.pop((ev.namespace, ev.job_id), None)
                self._escaped.pop(ev.id, None)
                self._captured.pop(ev.id, None)
            self.stats["unblocked"] += len(release)
        for ev in release:
            self._enqueue(ev)
        return len(release)

    def blocked_count(self) -> int:
        with self._lock:
            return len(self._by_job)

    def blocked_evals(self) -> List[Evaluation]:
        with self._lock:
            return list(self._by_job.values())
