"""Plan queue + serialized plan applier
(reference nomad/plan_queue.go + nomad/plan_apply.go — the
optimistic-concurrency linchpin).

Scheduler workers race against stale snapshots and submit plans; this
single applier thread is the only writer of placement results. Per plan:

  1. wait until the store has caught up to the plan's snapshot index
     (plan_apply.go:217 snapshotMinIndex);
  2. re-verify every touched node against the *latest* state with the
     same AllocsFit predicate the scheduler used (plan_apply.go:468,717
     evaluateNodePlan) — a node whose plan no longer fits (a concurrent
     plan won the race) is rejected wholesale. Verification fans out
     over a thread pool for plans touching many nodes (reference
     plan_apply_pool.go:21 EvaluatePool, half the cores);
  3. commit what survived (partial commit) and hand the scheduler a
     refresh index so it reschedules the remainder against fresher state
     (plan_apply.go:96-211). The commit (a raft round under a durable
     log) runs async while the next plan verifies against an optimistic
     overlay of the in-flight result (plan_apply.go:70-95 pipelining +
     :355-363 snapshot overlay).

Nodes that repeatedly reject plans feed a windowed BadNodeTracker
(reference plan_apply_node_tracker.go:17): a node whose rejection score
crosses the threshold is marked ineligible so broken kernels / stale
fingerprints stop eating scheduler retries cluster-wide.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..analysis.sanitizer import sanitized
from ..obs import RECORDER, TRACER
from ..structs import allocs_fit, enums
from ..structs.alloc import device_pairs
from ..structs.funcs import foreign_devices, node_instances
from ..structs.plan import Plan, PlanResult
from ..structs.resources import RESOURCE_DIMS


class PendingPlan:
    """A submitted plan awaiting the applier (reference plan_queue.go:33).

    `deadline` (absolute time.time(), nomadload) is stamped from the
    submitting request's bound deadline at enqueue; the applier drops a
    plan whose deadline already passed instead of verifying and
    committing work whose submitter has given up."""

    __slots__ = ("plan", "_event", "result", "error", "deadline")

    def __init__(self, plan: Plan, deadline: Optional[float] = None):
        self.plan = plan
        self._event = threading.Event()
        self.result: Optional[PlanResult] = None
        self.error: Optional[Exception] = None
        self.deadline = deadline

    def respond(self, result: Optional[PlanResult], error: Optional[Exception]) -> None:
        self.result = result
        self.error = error
        self._event.set()

    def wait(self, timeout: Optional[float] = None) -> PlanResult:
        if not self._event.wait(timeout):
            raise TimeoutError("plan apply timed out")
        if self.error is not None:
            raise self.error
        return self.result


@sanitized
class PlanQueue:
    """Priority queue of pending plans (reference plan_queue.go)."""

    def __init__(self):
        self._lock = threading.Condition()
        self._enabled = False
        self._heap: List[Tuple[int, int, PendingPlan]] = []
        self._seq = itertools.count()

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            self._enabled = enabled
            if not enabled:
                for _, _, p in self._heap:
                    p.respond(None, RuntimeError("plan queue disabled"))
                self._heap.clear()
            self._lock.notify_all()

    def enqueue(self, plan: Plan) -> PendingPlan:
        from . import loadctl

        pending = PendingPlan(plan, deadline=loadctl.current_deadline())
        with self._lock:
            if not self._enabled:
                pending.respond(None, RuntimeError("plan queue disabled"))
                return pending
            heapq.heappush(self._heap, (-plan.priority, next(self._seq), pending))
            self._lock.notify_all()
        return pending

    def dequeue(self, timeout: Optional[float] = None) -> Optional[PendingPlan]:
        # While disabled, WAIT rather than return: the applier polls
        # this in a loop, and an instant None turns that loop into a
        # full-CPU busy-wait for as long as the queue stays disabled
        # (nomadcheck plan_pipeline, preemption-bounded schedule).
        # set_enabled() notifies, so an enable wakes the sleeper.
        with self._lock:
            while True:
                if self._enabled and self._heap:
                    return heapq.heappop(self._heap)[2]
                if not self._lock.wait(timeout):
                    return None

    def depth(self) -> int:
        with self._lock:
            return len(self._heap)


class BadNodeTracker:
    """Windowed per-node plan-rejection scoring (reference
    plan_apply_node_tracker.go:17,40 + the CachedBadNodeTracker docs at
    monitoring-nomad.mdx:130-178). A node collecting `threshold`
    rejections inside `window` seconds is reported once per window; the
    server wires the report to mark the node ineligible."""

    def __init__(self, threshold: int = 15, window: float = 300.0,
                 on_bad_node=None):
        self.threshold = threshold
        self.window = window
        self.on_bad_node = on_bad_node
        self._lock = threading.Lock()
        self._events: Dict[str, List[float]] = {}
        self.stats = {"bad_nodes": 0}

    def add(self, node_id: str, now: Optional[float] = None) -> bool:
        now = now if now is not None else time.time()
        fire = False
        with self._lock:
            events = self._events.setdefault(node_id, [])
            events.append(now)
            cutoff = now - self.window
            while events and events[0] < cutoff:
                events.pop(0)
            if len(events) >= self.threshold:
                events.clear()  # report once, then start a fresh window
                fire = True
                self.stats["bad_nodes"] += 1
        if fire and self.on_bad_node is not None:
            try:
                self.on_bad_node(node_id)
            except Exception:
                pass
        return fire


class _BlockRows:
    """A block's live node rows in the row numbers of the store's dense
    columns: `rows` (k,), the `counts` (k,) of placements on them and
    the `vec` each placement takes (blocks are resource-only fresh
    placements by construction, so count x vec is the exact fit
    input), `row_set` for asking whether two blocks meet, whether the
    rows are `distinct`, and how many nodes they are. Kept on the
    block, whose layout is frozen at plan time: a block is read at its
    own verify and again, verify after verify, while it is in flight."""

    __slots__ = ("assignment", "rows", "counts", "vec", "row_set",
                 "distinct", "n_nodes")

    def __init__(self, cols, block):
        node_ids, counts = block.live_node_counts()
        self.assignment = cols.assignment
        self.rows = cols.rows(node_ids)
        self.counts = np.asarray(counts, dtype=np.float64)
        self.vec = block.allocated_vec
        self.row_set = frozenset(self.rows.tolist())
        self.distinct = len(self.row_set) == len(self.rows)
        # a node the store never saw has no row of its own: -1 may
        # stand for several, so the ids are counted where rows repeat
        self.n_nodes = (len(self.rows) if self.distinct
                        else len(set(node_ids)))

    @classmethod
    def of(cls, cols, block) -> "_BlockRows":
        kept = block._store_rows
        if kept is None or kept.assignment is not cols.assignment:
            kept = block._store_rows = cls(cols, block)
        return kept

    def usage(self) -> np.ndarray:
        return self.counts[:, None] * self.vec[None, :]


def _fits(used, asked_column, avail) -> np.ndarray:
    """used + asked <= avail in every dimension -> (M,) bool, float64 as
    _node_plan_valid's allocs_fit. A column at a time: inside a ufunc
    over more than 500 elements numpy lets go of the interpreter lock,
    and an applier that lets go of it among busy workers waits
    milliseconds to have it back (PERF.md section 6, PR 37); a column of
    a few hundred nodes stays under that."""
    ok = np.ones(len(used), dtype=bool)
    for d in range(RESOURCE_DIMS):
        ok &= used[:, d] + asked_column(d) <= avail[:, d]
    return ok


class _OverlaySnapshot:
    """In-flight plan results layered over a snapshot (oldest first),
    exposing just the reads _evaluate performs — so a new plan
    verifies against "state as of every pending commit" while those raft
    rounds are still in the air (reference plan_apply.go:355-363
    optimistic snapshot). More than one result can be pending at once:
    commit N can be running while commit N+1 waits behind it."""

    def __init__(self, snap, results: List[PlanResult]):
        self._snap = snap
        self._replaced: Dict[str, dict] = {}
        for result in results:  # later results override earlier ones
            for node_id in (set(result.node_allocation)
                            | set(result.node_update)
                            | set(result.node_preemptions)):
                by_id = self._replaced.setdefault(node_id, {})
                for bucket in (result.node_update, result.node_preemptions,
                               result.node_allocation):
                    for a in bucket.get(node_id, ()):
                        by_id[a.id] = a
        # a result listed as in flight whose commit landed before this
        # snapshot was taken is in the snapshot already: a row nets
        # itself out by its id (inflight below), a block has to
        # be left out here, or its nodes read twice as full and the
        # next plan's rows on them are rejected. The snapshot that
        # decides is the one whose generation the usage is read at
        # (node_columns), so a commit landing after it is in neither
        # and is added
        self._blocks = [block for result in results
                        for block in result.alloc_blocks
                        if snap.alloc_block_by_id(block.id) is None]
        # node id -> [(block, row)] of those blocks: the per-node reads'
        # index (allocs_by_node), built when the exact path first asks
        self._block_rows: Optional[Dict[str, list]] = None
        # ids an in-flight result stops or preempts (a later plan that
        # names one as its victim frees nothing by it), built when a
        # plan with column evictions first asks
        self._results = results
        self._gone: Optional[set] = None

    def node_by_id(self, node_id):
        return self._snap.node_by_id(node_id)

    def node_columns(self):
        return self._snap.node_columns()

    def inflight(self, cols) -> Tuple[List[_BlockRows], Optional[tuple]]:
        """What the in-flight results add to what the snapshot's columns
        hold: their blocks' live node rows, and for the nodes they touch
        with rows a (rows, usage) pair of what each row will count less
        what the snapshot counts for it already (the usage rows' `not
        terminal_status()` predicate)."""
        blocks = [_BlockRows.of(cols, block) for block in self._blocks]
        if not self._replaced:
            return blocks, None
        deltas = []
        for by_id in self._replaced.values():
            delta = np.zeros(RESOURCE_DIMS)
            for aid, a in by_id.items():
                if not a.terminal_status():
                    delta += a.allocated_vec
                base_a = self._snap.alloc_by_id(aid)
                if base_a is not None and not base_a.terminal_status():
                    delta -= base_a.allocated_vec
            deltas.append(delta)
        return blocks, (cols.rows(list(self._replaced)), np.asarray(deltas))

    def _block_rows_of(self, node_id: str) -> list:
        rows = self._block_rows
        if rows is None:
            rows = self._block_rows = {}
            for block in self._blocks:
                for m in block.live_rows():
                    rows.setdefault(block.node_ids[m], []).append((block, m))
        return rows.get(node_id, ())

    def allocs_by_node(self, node_id):
        overlay = self._replaced.get(node_id)
        rows = self._block_rows_of(node_id)
        base = self._snap.allocs_by_node(node_id)
        if not overlay and not rows:
            return base
        out = ([overlay.get(a.id, a) for a in base] if overlay
               else list(base))
        if overlay:
            have = {a.id for a in base}
            out.extend(a for aid, a in overlay.items() if aid not in have)
        for block, m in rows:
            out.extend(block.allocs_for_row(m))
        return out

    def ports_by_node(self, node_id):
        """allocs_by_node's port numbers, read as the snapshot reads
        them: the in-flight rows replace theirs by id, and the in-flight
        blocks add their port column at the node's rows."""
        overlay = self._replaced.get(node_id)
        if overlay:
            out = self._snap.ports_by_node(node_id, skip=overlay)
            for a in overlay.values():
                if not a.terminal_status():
                    out.extend(p.value for p in a.allocated_ports)
        else:
            out = self._snap.ports_by_node(node_id)
        for block, m in self._block_rows_of(node_id):
            for _, values in block.ports_for_row(m):
                out.extend(values)
        return out

    def devices_by_node(self, node_id):
        """allocs_by_node's device instances, read as ports_by_node
        reads ports."""
        overlay = self._replaced.get(node_id)
        out = self._snap.devices_by_node(node_id, skip=overlay or ())
        for a in (overlay or {}).values():
            if not a.terminal_status():
                out.extend(device_pairs(a))
        for block, m in self._block_rows_of(node_id):
            for _, pairs in block.devices_for_row(m):
                out.extend(pairs)
        return out

    def live_mask(self, alloc_ids):
        """The snapshot's liveness with what the in-flight results stop
        or preempt taken out."""
        gone = self._gone
        if gone is None:
            gone = self._gone = {
                aid for by_id in self._replaced.values()
                for aid, a in by_id.items() if a.terminal_status()}
            for result in self._results:
                if result.preempted:
                    gone.update(result.preempted.alloc_ids)
        live = self._snap.live_mask(alloc_ids)
        if gone:
            live &= np.fromiter((aid not in gone for aid in alloc_ids),
                                bool, len(alloc_ids))
        return live

    def job_by_id(self, job_id, namespace="default"):
        return self._snap.job_by_id(job_id, namespace)

    def alloc_by_id(self, alloc_id):
        for by_id in self._replaced.values():
            if alloc_id in by_id:
                return by_id[alloc_id]
        return self._snap.alloc_by_id(alloc_id)

    def volume_by_id(self, vol_id, namespace="default"):
        return self._snap.volume_by_id(vol_id, namespace)

    def overlay_writer_volumes(self) -> set:
        """(namespace, source) pairs the in-flight placements will claim
        for write at commit — claims land inside the store transaction,
        so the overlay must surface them or back-to-back pipelined plans
        could each think a single-writer volume is free. Slightly
        conservative: updates that already hold the claim also count."""
        from ..structs.volumes import csi_writer_sources

        out = set()
        for by_id in self._replaced.values():
            for a in by_id.values():
                out.update(csi_writer_sources(a))
        return out


class _CommitEntry:
    """One verified plan waiting on the batching commit thread — or,
    with plan=None, a bare eval-status update riding the same batch
    (payload pre-built, no verification, no overlay cell)."""

    __slots__ = ("plan", "result", "rejected", "verify_gen", "cell",
                 "future", "error", "payload", "trace", "t0")

    def __init__(self, plan, result, rejected, verify_gen, cell, future,
                 payload=None):
        self.plan = plan
        self.result = result
        self.rejected = rejected
        self.verify_gen = verify_gen
        self.cell = cell
        self.future = future
        self.error: Optional[Exception] = None
        self.payload = payload
        # obs: the eval whose plan this is (None for bare eval updates)
        # and the entry's creation time — _respond records the
        # entry-to-verdict window as the plan.commit span from these
        self.trace = getattr(plan, "eval_id", None) or None
        self.t0 = time.time()


class PlanApplier:
    """The serialized applier goroutine (reference plan_apply.go:96 planApply)."""

    # Per-node verification CAN fan out over the pool (set this lower),
    # but _node_plan_valid is pure-Python and GIL-bound: measured at 5K
    # touched nodes the pool runs ~3x SLOWER than the serial loop,
    # unlike the reference's Go EvaluatePool. Serial is
    # therefore the default; the pool pays off only if the per-node check
    # grows GIL-releasing work (native fit kernels, IO).
    PARALLEL_THRESHOLD = 1 << 30

    # Commit coalescing cap: one raft round (fsync + quorum) covers at
    # most this many verified plans. Far above what verification can
    # queue behind one round trip in practice; bounds worst-case
    # batch-failure fallback work.
    COMMIT_BATCH_MAX = 64

    # Commit rounds in flight at once when the store can propose
    # without waiting (RaftStore.propose_async under a group-commit
    # raft node). The replicated round costs ~1 disk fsync of latency
    # quiet but inflates several-fold under scheduler thread load (GIL
    # handoffs on the propose→log-writer→replicate→ack→apply path);
    # overlapping rounds hides that latency the same way pipelined
    # replication hides the follower round trip. Raft log order =
    # propose order, so apply order across overlapping rounds is
    # exactly the serialized path's.
    COMMIT_PIPELINE_DEPTH = 4

    def __init__(self, store, queue: PlanQueue, logger=None,
                 pool_workers: Optional[int] = None,
                 bad_node_tracker: Optional[BadNodeTracker] = None):
        import os

        self.store = store
        self.queue = queue
        self.logger = logger
        self._thread: Optional[threading.Thread] = None
        self._commit_thread: Optional[threading.Thread] = None
        # verified-and-waiting commit entries the commit thread coalesces
        self._commit_q: "deque[_CommitEntry]" = deque()
        self._commit_cond = threading.Condition()
        self._stop = threading.Event()
        self.stats = {"applied": 0, "nodes_verified": 0,
                      "nodes_verified_columnar": 0, "nodes_rejected": 0,
                      "port_collisions": 0, "device_collisions": 0,
                      "partial_commits": 0,
                      "commit_batches": 0, "batched_commits": 0,
                      "batched_eval_updates": 0}
        # commits are serialized through the commit thread, but the
        # synchronous apply() entrypoint can run concurrently with the
        # loop; counters get their own leaf lock
        self._stats_lock = threading.Lock()
        # reference plan_apply_pool.go: half the cores
        self.pool_workers = pool_workers or max(2, (os.cpu_count() or 2) // 2)
        self._pool: Optional[ThreadPoolExecutor] = None
        self.bad_nodes = bad_node_tracker or BadNodeTracker()
        # Poison generation for the pipelined overlay: bumped whenever a
        # commit fails OR a commit-time re-verification rewrites a result
        # that later plans' overlays already included. A plan whose
        # verify-time generation is stale re-verifies against the real
        # store before committing (commits are serialized, so by then
        # every predecessor has landed or failed).
        self._poison_gen = 0
        # per-thread sparse accumulator of the array fit check: verify
        # runs on the applier's thread, on the commit thread when it
        # re-verifies, and on whoever calls apply()
        self._scratch = threading.local()
        # called with a committed plan's preemption evaluations (the
        # server enqueues them); None leaves them in the store alone
        self.on_evals = None

    def start(self) -> None:
        self._stop.clear()
        self._pool = ThreadPoolExecutor(max_workers=self.pool_workers,
                                        thread_name_prefix="plan-verify")
        self._commit_thread = threading.Thread(
            target=self._run_commit, daemon=True, name="plan-commit")
        self._commit_thread.start()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="plan-applier")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self.queue.set_enabled(False)
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        if self._commit_thread is not None:
            with self._commit_cond:
                self._commit_cond.notify_all()
            self._commit_thread.join(timeout=5.0)
            # drain anything that raced in after the commit thread's
            # final queue check: an entry left here would strand its
            # submitter until nack timeout (found by the nomadcheck
            # plan_pipeline scenario). _commit_thread goes to None in
            # the same lock hold, so _run/submit_eval_updates either
            # append before this drain (failed here) or observe
            # None+stop and refuse.
            with self._commit_cond:
                stranded = list(self._commit_q)
                self._commit_q.clear()
                self._commit_thread = None
            for entry in stranded:
                if not entry.future.done():
                    entry.future.set_exception(
                        RuntimeError("plan applier stopped"))
        if self._pool is not None:
            self._pool.shutdown(wait=False)

    def _run(self) -> None:
        # pipeline state: every submitted-but-unlanded commit, oldest
        # first. Each entry's CELL holds the result its overlay readers
        # should see; commit-time re-verification rewrites the cell.
        # Seqlock discipline with _poison_gen: writers update the cell
        # THEN bump the generation; readers read the generation THEN the
        # cells, and re-verify at commit if the generation moved.
        from .metrics import REGISTRY

        inflight: List[Tuple[Future, dict]] = []
        while not self._stop.is_set():
            pending = self.queue.dequeue(timeout=0.2)
            REGISTRY.set_gauge("nomad.plan.queue_depth", self.queue.depth())
            if pending is None:
                continue
            from . import loadctl

            if loadctl.check_expired(pending.deadline, "plan_apply"):
                # submitter's deadline passed while the plan queued:
                # verifying + committing it would be wasted work the
                # worker already timed out on (nomadload)
                pending.respond(None, TimeoutError(
                    "plan deadline expired before apply"))
                continue
            try:
                inflight = [(f, c) for f, c in inflight if not f.done()]
                verify_gen = self._poison_gen
                overlays = [c["result"] for _, c in inflight]
                result, rejected = self._verify(pending.plan, overlays)
                # commits are serialized in submission order through
                # the commit thread, which coalesces every
                # verified-and-waiting plan into one store/raft round;
                # the submitter is answered from the future's callback
                # the moment its commit lands
                cell = {"result": result}
                fut: Future = Future()
                fut.add_done_callback(self._responder(pending))
                entry = _CommitEntry(pending.plan, result, rejected,
                                     verify_gen, cell, fut)
                with self._commit_cond:
                    if self._stop.is_set() and self._commit_thread is None:
                        # stop() already drained the commit queue;
                        # an entry appended now is never answered
                        raise RuntimeError("plan applier stopped")
                    self._commit_q.append(entry)
                    self._commit_cond.notify()
                inflight.append((fut, cell))
            except Exception as e:  # surface to the submitting worker
                if self.logger:
                    self.logger.exception("plan apply failed")
                pending.respond(None, e)

    @staticmethod
    def _responder(pending: "PendingPlan"):
        def done(fut: Future) -> None:
            err = fut.exception()
            if err is not None:
                pending.respond(None, err)
            else:
                pending.respond(fut.result(), None)
        return done

    # -- verify (parallel) --

    def _verify(self, plan, overlay=None):
        from .metrics import REGISTRY

        with REGISTRY.time("nomad.plan.evaluate"), \
                TRACER.span("plan.verify", cpu=True,
                            trace=getattr(plan, "eval_id", None) or None):
            return self._verify_inner(plan, overlay)

    def _verify_inner(self, plan: Plan,
                overlay_results: Optional[List[PlanResult]] = None,
                ) -> Tuple[PlanResult, List[str]]:
        # catch up to the snapshot the scheduler planned against
        if plan.snapshot_index:
            snap = self.store.snapshot_min_index(plan.snapshot_index)
        else:
            snap = self.store.snapshot()
        if overlay_results:
            snap = _OverlaySnapshot(snap, overlay_results)
        return self._evaluate(snap, plan)

    # -- the serialized commit --

    @staticmethod
    def _result_equal(r1: PlanResult, rej1: List[str],
                      r2: PlanResult, rej2: List[str]) -> bool:
        if sorted(rej1) != sorted(rej2):
            return False
        for attr in ("node_allocation", "node_update", "node_preemptions"):
            d1, d2 = getattr(r1, attr), getattr(r2, attr)
            if set(d1) != set(d2):
                return False
            for k in d1:
                if [a.id for a in d1[k]] != [a.id for a in d2[k]]:
                    return False
        b1 = {(b.id, b.rejected_rows) for b in r1.alloc_blocks}
        b2 = {(b.id, b.rejected_rows) for b in r2.alloc_blocks}
        return b1 == b2

    # -- the batching commit thread --

    def _run_commit(self) -> None:
        """Group commit for plans: drain every verified-and-waiting
        entry and land the lot as ONE upsert_plan_results_batch — under
        raft, one replicated command, one fsync+quorum round (riding the
        log writer's append_batch) — instead of one round per plan.
        Entries keep submission order, so the pipelined-overlay
        invariants are those of committing one plan at a time.

        When the store can propose without waiting (a group-commit raft
        node), commit rounds additionally PIPELINE up to
        COMMIT_PIPELINE_DEPTH deep: round K+1 is verified and proposed
        while K is still replicating. Raft log order equals propose
        order from this single thread, so the FSM applies the rounds in
        exactly the order they were built; responses are reaped oldest
        round first, preserving the serialized path's answer order."""
        if getattr(self.store, "can_propose_async", False):
            return self._run_commit_pipelined()
        while True:
            with self._commit_cond:
                while not self._commit_q and not self._stop.is_set():
                    self._commit_cond.wait(0.2)
                if not self._commit_q:
                    if self._stop.is_set():
                        return
                    continue
                entries = []
                while self._commit_q and len(entries) < self.COMMIT_BATCH_MAX:
                    entries.append(self._commit_q.popleft())
            try:
                self._commit_entries(entries)
            except Exception as e:
                # belt-and-braces: _commit_entries contains per-entry
                # handling; anything escaping here must still answer the
                # submitters or their workers block until nack timeout
                if self.logger:
                    self.logger.exception("plan commit batch failed")
                for entry in entries:
                    if not entry.future.done():
                        entry.future.set_exception(e)

    def _run_commit_pipelined(self) -> None:
        """The overlapping-rounds variant of _run_commit, split across
        two threads so a round in flight never stalls the next one:

        - THIS thread (the proposer) drains the commit queue, verifies
          and PROPOSES rounds back-to-back — the workload is a convoy
          (every submitter blocks on its round, then produces its next
          write only after the round lands), so the entries for round
          K+1 arrive precisely while round K replicates; a proposer
          that waited for K would re-serialize the rounds it is meant
          to overlap.
        - The reap thread waits on rounds OLDEST FIRST and answers
          their submitters, preserving the serialized path's response
          order. The reap deque doubles as the in-flight window the
          proposer overlays (rounds leave it only after landing) and
          as backpressure: the proposer stalls at COMMIT_PIPELINE_DEPTH
          unreaped rounds.

        On stop, the proposer drains the queue, then the reaper drains
        every in-flight round — submitters are always answered."""
        reap_q: deque = deque()
        reap_cond = threading.Condition()
        reap_done = threading.Event()

        def reaper() -> None:
            while True:
                with reap_cond:
                    while not reap_q and not reap_done.is_set():
                        reap_cond.wait(0.2)
                    if not reap_q:
                        return
                    # peek, don't pop: the proposer must keep
                    # overlaying this round until it has LANDED
                    round_ = reap_q[0]
                try:
                    self._finish_round(round_)
                except Exception as e:
                    # belt-and-braces: _finish_round answers per-entry;
                    # anything escaping must still answer the rest or
                    # their workers block until nack timeout
                    if self.logger:
                        self.logger.exception("plan commit reap failed")
                    for entry in round_["entries"]:
                        if not entry.future.done():
                            entry.future.set_exception(e)
                with reap_cond:
                    reap_q.popleft()
                    reap_cond.notify_all()  # release backpressure

        reap_thread = threading.Thread(target=reaper, daemon=True,
                                       name="plan-commit-reap")
        reap_thread.start()
        try:
            while True:
                entries: List[_CommitEntry] = []
                with self._commit_cond:
                    while not self._commit_q and not self._stop.is_set():
                        self._commit_cond.wait(0.2)
                    while self._commit_q \
                            and len(entries) < self.COMMIT_BATCH_MAX:
                        entries.append(self._commit_q.popleft())
                if not entries:
                    return  # stopped with a drained queue
                with reap_cond:
                    while len(reap_q) >= self.COMMIT_PIPELINE_DEPTH:
                        reap_cond.wait(0.2)
                    inflight = list(reap_q)
                try:
                    round_ = self._begin_round(entries, inflight)
                except Exception as e:
                    if self.logger:
                        self.logger.exception("plan commit round failed")
                    for entry in entries:
                        if not entry.future.done():
                            entry.future.set_exception(e)
                    continue
                with reap_cond:
                    reap_q.append(round_)
                    reap_cond.notify_all()
        finally:
            reap_done.set()
            with reap_cond:
                reap_cond.notify_all()
            reap_thread.join(timeout=5.0)

    def _commit_entries(self, entries: List[_CommitEntry]) -> None:
        plans = self._round_prologue(entries)
        # 1: poisoned-overlay re-verification, in order. In-batch
        # predecessors have NOT landed yet, so a stale entry re-verifies
        # against the bare store overlaid with its predecessors' current
        # cells (they land atomically with it). Eval-only entries carry
        # no placements: nothing to verify.
        self._reverify_stale(plans, [])
        # 2: one transaction for the whole batch
        writers = self._writers_for(entries)
        if writers:
            # cpu_s: wall less the thread's own CPU seconds is time
            # this thread was blocked or waiting for the interpreter lock
            with TRACER.span("plan.commit_round", cpu=True, n=len(writers),
                             traces=[e.trace for e in entries if e.trace]):
                try:
                    index = self.store.upsert_plan_results_batch(
                        [p for _, p in writers])
                    for e, _ in writers:
                        if e.result is not None:
                            e.result.alloc_index = index
                except Exception:
                    if self.logger:
                        self.logger.exception(
                            "batched plan commit failed; retrying per-plan")
                    self._commit_fallback(writers)
        # 3: respond in order
        self._respond(entries)

    def _round_prologue(self, entries: List[_CommitEntry]
                        ) -> List[_CommitEntry]:
        """Stats + gauges for one commit round; returns the plan-backed
        entries (the rest are bare eval updates)."""
        from .metrics import REGISTRY

        plans = [e for e in entries if e.plan is not None]
        REGISTRY.set_gauge("nomad.plan.commit_batch_size", len(entries))
        with self._stats_lock:
            self.stats["commit_batches"] += 1
            self.stats["batched_commits"] += len(plans)
            self.stats["batched_eval_updates"] += len(entries) - len(plans)
        return plans

    def _poison(self, cell: Optional[dict], result: PlanResult) -> None:
        """Rewrite an overlay cell and bump the poison generation as
        one guarded step. With pipelined rounds there are TWO writer
        threads (the proposer re-verifying stale entries, the reaper
        failing/falling-back rounds); readers stay lock-free — the
        generation check is a bare int read — per the seqlock
        discipline described in _run."""
        with self._stats_lock:
            if cell is not None:
                cell["result"] = result  # data first...
            self._poison_gen += 1        # ...then the version bump

    def _reverify_stale(self, plans: List[_CommitEntry],
                        prior: List[_CommitEntry]) -> None:
        """Phase 1: entries whose verify-time generation went stale
        re-verify against the bare store overlaid with every
        predecessor that has not landed yet — `prior` (plan entries of
        in-flight pipelined rounds, oldest first) plus this round's
        earlier entries. All of them enter the raft log strictly before
        this entry, so overlaying their current cells is exact."""
        done: List[_CommitEntry] = list(prior)
        for e in plans:
            if self._poison_gen != e.verify_gen:
                overlays = [p.cell["result"] for p in done] or None
                new_result, new_rejected = self._verify(e.plan, overlays)
                if not self._result_equal(e.result, e.rejected,
                                          new_result, new_rejected):
                    self._poison(e.cell, new_result)
                e.result, e.rejected = new_result, new_rejected
            done.append(e)

    def _writers_for(self, entries: List[_CommitEntry]
                     ) -> List[Tuple[_CommitEntry, dict]]:
        payloads = [e.payload if e.plan is None
                    else self._payload_for(e.plan, e.result)
                    for e in entries]
        return [(e, p) for e, p in zip(entries, payloads) if p is not None]

    def _respond(self, entries: List[_CommitEntry]) -> None:
        """Phase 3: answer every submitter, in order."""
        for e in entries:
            if e.error is not None:
                self._poison(e.cell, PlanResult())  # nothing of e landed
                e.future.set_exception(e.error)
            elif e.plan is None:
                e.future.set_result(None)
            else:
                e.future.set_result(
                    self._finalize(e.plan, e.result, e.rejected))
            if e.trace is not None:
                # the entry's whole commit-side life: queued at the
                # commit thread -> verdict delivered
                TRACER.add_span("plan.commit", e.t0, time.time(),
                                trace=e.trace,
                                rejected=len(e.rejected or ()),
                                failed=e.error is not None)

    # -- the pipelined rounds (store.can_propose_async) --

    def _begin_round(self, entries: List[_CommitEntry],
                     inflight: "deque") -> dict:
        """Verify and PROPOSE one commit round without waiting for the
        raft commit. Phase-1 overlays must include the plan entries of
        every round still in flight — they precede this round in the
        log but have not applied yet. A propose failure (lost
        leadership, stopped node) is recorded on the round and handled
        at reap time exactly like a failed batch transaction."""
        plans = self._round_prologue(entries)
        prior = [e for r in inflight for e in r["plans"]]
        self._reverify_stale(plans, prior)
        writers = self._writers_for(entries)
        round_ = {"entries": entries, "plans": plans, "writers": writers,
                  "prop": None, "error": None, "t0": time.time(),
                  "cpu_s": 0.0}
        cpu0 = time.thread_time()
        if writers:
            with TRACER.span("plan.propose", n=len(writers),
                             traces=[e.trace for e in entries if e.trace]):
                try:
                    round_["prop"] = self.store.propose_async(
                        "upsert_plan_results_batch",
                        [p for _, p in writers])
                except Exception as err:
                    round_["error"] = err
        if round_["error"] is not None:
            # The round's outcome is now ambiguous until the reap
            # thread's fallback resolves it, but a successor round
            # may be verified and proposed before then. Make the
            # overlay cells conservative in BOTH directions: keep
            # the placements (they may still land via the fallback
            # — successors must not reuse that capacity) and drop
            # the stops/preemptions (they may never land —
            # successors must not move into capacity they "freed").
            for e in plans:
                conservative = PlanResult()
                conservative.node_allocation = dict(
                    e.result.node_allocation)
                conservative.alloc_blocks = list(e.result.alloc_blocks)
                self._poison(e.cell, conservative)
        round_["cpu_s"] = time.thread_time() - cpu0
        return round_

    def _finish_round(self, round_: dict) -> None:
        """Reap one in-flight round: wait for its raft apply, then
        respond. A failed wait falls back to per-plan commits — the
        retried payloads land AFTER any younger in-flight rounds, but
        every payload is an upsert keyed by alloc/eval id, so a round
        that actually landed before the ambiguous timeout re-applies as
        a no-op and a genuinely lost round converges to the same final
        state the in-order apply would have produced."""
        writers = round_["writers"]
        prop = round_["prop"]
        if prop is not None:
            with TRACER.span("plan.commit_wait", n=len(writers),
                             traces=[e.trace for e in round_["entries"]
                                     if e.trace]):
                try:
                    cpu0 = time.thread_time()
                    index = self.store.wait_applied(prop, timeout=30.0)
                    for e, _ in writers:
                        if e.result is not None:
                            e.result.alloc_index = index
                    # the round as the serialized path's span times it:
                    # the store transaction, here from its proposal to
                    # applied. Two threads share it: cpu_s is the
                    # proposer's CPU seconds over the propose plus this
                    # thread's over the wait, so wall less cpu_s is the
                    # wait for the quorum and for the interpreter lock
                    TRACER.add_span(
                        "plan.commit_round", round_["t0"], time.time(),
                        n=len(writers), pipelined=True,
                        cpu_s=round_["cpu_s"] + time.thread_time() - cpu0,
                        traces=[e.trace for e in round_["entries"]
                                if e.trace])
                except Exception:
                    if self.logger:
                        self.logger.exception(
                            "pipelined plan commit failed; "
                            "retrying per-plan")
                    self._commit_fallback(writers)
        elif round_["error"] is not None and writers:
            if self.logger:
                self.logger.error(
                    "plan commit propose failed; retrying per-plan: %s",
                    round_["error"])
            self._commit_fallback(writers)
        self._respond(round_["entries"])

    def _commit_fallback(self, writers: List[Tuple[_CommitEntry, dict]]
                         ) -> None:
        """The whole-batch transaction failed (nothing landed): land
        each plan individually so one poisoned plan fails alone. After
        any individual failure, later entries re-verify against the bare
        store — by then every predecessor has landed individually or
        failed, so the store is exact again."""
        dirty = False
        for e, payload in writers:
            try:
                if dirty and e.plan is not None:
                    new_result, new_rejected = self._verify(e.plan, None)
                    if not self._result_equal(e.result, e.rejected,
                                              new_result, new_rejected):
                        self._poison(e.cell, new_result)
                    e.result, e.rejected = new_result, new_rejected
                    payload = self._payload_for(e.plan, e.result)
                if payload is not None:
                    index = self.store.upsert_plan_results(**payload)
                    if e.result is not None:
                        e.result.alloc_index = index
            except Exception as err:
                e.error = err
                dirty = True

    @staticmethod
    def _payload_for(plan: Plan, result: PlanResult) -> Optional[dict]:
        """The store-write kwargs for one verified plan, or None when
        the plan has nothing left to write (fully rejected).

        Plan normalization (reference nomad 0.9 plan normalization,
        plan_normalization.go + structs Allocation.Job denormalization):
        every Allocation embeds its full Job, which measured as ~70% of
        the replicated bytes for small service plans — paid again at
        every stage of the write path (log-writer deepcopy, durable-log
        json, follower persistence x2, FSM decode). Ship the plan's job
        ONCE in the payload and strip it from each alloc via shallow
        copies (the scheduler's objects and the overlay cells keep
        theirs); the FSM re-attaches at apply
        (StateStore._rehydrate_alloc_jobs)."""
        import copy as _copy

        def stripped(allocs: list) -> list:
            out = []
            for a in allocs:
                if a.job is not None:
                    a = _copy.copy(a)
                    a.job = None
                out.append(a)
            return out

        placements, stops, preemptions = [], [], []
        for allocs in result.node_allocation.values():
            placements.extend(allocs)
        for allocs in result.node_update.values():
            stops.extend(allocs)
        for allocs in result.node_preemptions.values():
            preemptions.extend(allocs)
        if not (placements or stops or preemptions or result.alloc_blocks
                or result.preempted or result.deployment is not None
                or result.deployment_updates or plan.eval_updates):
            return None
        payload = {
            "result_allocs": stripped(placements),
            "stopped_allocs": stripped(stops),
            "preempted_allocs": stripped(preemptions),
            "deployment": result.deployment,
            "deployment_updates": result.deployment_updates,
            "evals": list(plan.eval_updates) + result.preemption_evals,
            "alloc_blocks": list(result.alloc_blocks),
            "job": plan.job,
        }
        if result.preempted:
            # only where there are any: a payload without them encodes
            # as it did before they existed
            payload["preempted_positions"] = result.preempted
        return payload

    def _commit(self, plan: Plan, result: PlanResult,
                rejected: List[str]) -> PlanResult:
        payload = self._payload_for(plan, result)
        if payload is not None:
            index = self.store.upsert_plan_results(**payload)
            result.alloc_index = index
        return self._finalize(plan, result, rejected)

    def _finalize(self, plan: Plan, result: PlanResult,
                  rejected: List[str]) -> PlanResult:
        from .metrics import REGISTRY

        with self._stats_lock:
            self.stats["applied"] += 1
            if rejected:
                self.stats["nodes_rejected"] += len(rejected)
                self.stats["partial_commits"] += 1
        REGISTRY.incr("nomad.plan.submit")
        if rejected:
            REGISTRY.incr("nomad.plan.node_rejected", len(rejected))
            result.refresh_index = self.store.latest_index
            result.rejected_nodes = rejected
            RECORDER.record("plan", "partial_reject",
                            eval=(plan.eval_id or "")[:8],
                            nodes=[n[:8] for n in rejected[:4]],
                            n=len(rejected))
        else:
            RECORDER.record("plan", "applied",
                            eval=(plan.eval_id or "")[:8])
        # post-apply hooks run HERE, synchronously with the commit (not
        # in the scheduler after submit returns): the solver service's
        # confirm() must close a solve's ledger entry as close as
        # possible to the moment its usage lands in the store, or a
        # resync in the window counts the placements twice (store row +
        # still-open entry) and the inflated carry under-places for up
        # to RESYNC_SOLVES solves
        for hook in plan.post_apply_hooks:
            try:
                hook(result)
            except Exception:
                if self.logger:
                    self.logger.exception("post-apply hook failed")
        if result.preemption_evals and self.on_evals is not None:
            # committed with the plan: now the broker may have them
            # (reference fsm.go applyPlanResults -> handleUpsertedEvals)
            self.on_evals(result.preemption_evals)
        return result

    def submit_eval_updates(self, evals) -> Future:
        """Durably persist eval status updates by riding the plan-commit
        batch: every eval update and plan commit waiting at the commit
        thread lands as ONE replicated command (one fsync + quorum
        round) instead of a dedicated upsert_evals round per eval — the
        second half of the per-eval raft cost the batched pipeline
        amortizes. The returned future resolves (to None) when the
        update is committed; callers needing durability-before-ack wait
        on it, preserving the direct write's semantics exactly."""
        fut: Future = Future()
        entry = _CommitEntry(None, None, (), 0, None, fut,
                             payload={"evals": list(evals)})
        with self._commit_cond:
            if self._stop.is_set() or self._commit_thread is None:
                # the commit thread may already have drained and exited
                # (or never started); an entry appended now would never
                # be answered
                raise RuntimeError("plan applier not running")
            self._commit_q.append(entry)
            self._commit_cond.notify()
        return fut

    def apply(self, plan: Plan) -> PlanResult:
        """Synchronous verify+commit (tests and direct callers; the
        applier loop pipelines the same two halves)."""
        result, rejected = self._verify(plan, None)
        return self._commit(plan, result, rejected)

    # A plan of rows alone that touches fewer array-path nodes than this
    # takes the python loop: the arrays' set-up cost (some twenty numpy
    # calls whatever the size) wins from about a dozen nodes on. A plan
    # with a block takes the arrays whatever its size.
    VECTOR_THRESHOLD = 16

    def _evaluate(self, snap, plan: Plan) -> Tuple[PlanResult, List[str]]:
        """Per-node re-verification (reference plan_apply.go:468
        evaluatePlan + :717 evaluateNodePlan). all_at_once plans commit
        fully or not at all (structs Plan.AllAtOnce).

        The GIL-free scale path (reference plan_apply_pool.go:21
        EvaluatePool's role): nodes touched ONLY by new placements that
        carry no cores — a block's nodes and the entire bulk-placement
        shape, rows with no port or device — skip the per-node
        alloc walk entirely. Their fit check is usage_row + sum(new
        vecs) <= available as arrays from the plan to the verdict
        (_columnar_verdicts); the accounting is exactly
        _node_plan_valid's (existing filters `not terminal_status()`,
        the usage rows' predicate). A placement without ports, devices
        or cores makes no new collision possible; the port numbers and
        device instances of a block that carries them are checked on its
        nodes by allocs_fit's rule without an Allocation
        (_block_id_verdicts). Everything else keeps the exact python
        check."""
        from .metrics import REGISTRY

        result = PlanResult()
        row_nodes = (set(plan.node_allocation) | set(plan.node_update)
                     | set(plan.node_preemptions))
        exact = [nid for nid in sorted(row_nodes)
                 if nid in plan.node_update or nid in plan.node_preemptions
                 or not all(a.create_index == 0 and not a.allocated_ports
                            and not a.allocated_devices
                            and not a.allocated_cores
                            for a in plan.node_allocation.get(nid, ()))]
        fresh = sorted(row_nodes.difference(exact))
        if len(fresh) < self.VECTOR_THRESHOLD and not plan.alloc_blocks:
            exact, fresh = sorted(row_nodes), []
        n_columnar, bad = self._columnar_verdicts(snap, plan, fresh, exact)
        if len(exact) >= self.PARALLEL_THRESHOLD and self._pool is not None:
            valid = self._pool.map(
                lambda nid: self._node_plan_valid(snap, plan, nid), exact)
        else:
            valid = (self._node_plan_valid(snap, plan, nid) for nid in exact)
        bad.update(itertools.compress(exact, (not v for v in valid)))
        # the denominator of the rejected share: every node row given a
        # verdict, a re-verified plan's rows again (nodes_rejected
        # counts the final verdict once, in _finalize); and how many of
        # them the array path gave it
        with self._stats_lock:
            self.stats["nodes_verified"] += n_columnar + len(exact)
            self.stats["nodes_verified_columnar"] += n_columnar
        REGISTRY.incr("nomad.plan.nodes_verified", n_columnar + len(exact))
        REGISTRY.incr("nomad.plan.nodes_verified_columnar", n_columnar)
        # only per-node plan invalidity feeds the tracker — losing a
        # cross-node single-writer-volume race says nothing about the
        # node's health (reference evaluateNodePlan-only accounting,
        # plan_apply_node_tracker.go)
        for node_id in sorted(bad):
            # san-ok: BadNodeTracker.add locks internally
            self.bad_nodes.add(node_id)
        rejected = sorted(bad | self._volume_rejections(snap, plan))
        if rejected and plan.all_at_once:
            # all-or-nothing plan: reject everything
            return result, sorted(row_nodes.union(
                *(b.live_node_counts()[0] for b in plan.alloc_blocks)))
        for node_id in sorted(row_nodes.difference(rejected)):
            if node_id in plan.node_allocation:
                result.node_allocation[node_id] = plan.node_allocation[node_id]
            if node_id in plan.node_update:
                result.node_update[node_id] = plan.node_update[node_id]
            if node_id in plan.node_preemptions:
                result.node_preemptions[node_id] = plan.node_preemptions[node_id]
        # no rejected node is the common case: the plan's blocks as they are
        for block in plan.alloc_blocks:
            sliced = block.without_nodes(rejected) if rejected else block
            if any(True for _ in sliced.live_rows()):
                result.alloc_blocks.append(sliced)
        if plan.preempted:
            taken = (plan.preempted.without_nodes(rejected) if rejected
                     else plan.preempted)
            # a victim gone since: nothing of it to evict
            taken = taken.take(np.flatnonzero(
                snap.live_mask(taken.alloc_ids)).tolist())
            result.preempted = taken if taken else None
        if result.node_preemptions or result.preempted:
            result.preemption_evals = self._preemption_evals(snap, result)
        result.deployment = plan.deployment
        result.deployment_updates = plan.deployment_updates
        return result, rejected

    @staticmethod
    def _preemption_evals(snap, result: PlanResult) -> list:
        """One pending evaluation triggered by "preemption" per job that
        loses allocations to this result, so that its scheduler places
        them again (reference plan_apply.go applyPlan PreemptionEvals).
        Span plan.preempt_evals (args `jobs`, `victims`); counter
        nomad.preempt.evals."""
        from ..structs.evaluation import Evaluation
        from ..utils import generate_uuid
        from .metrics import REGISTRY

        with TRACER.span("plan.preempt_evals") as span:
            jobs = {(a.namespace, a.job_id)
                    for allocs in result.node_preemptions.values()
                    for a in allocs}
            taken = result.preempted
            if taken:
                jobs.update((g[0], g[1]) for g in taken.groups)
            now = time.time()
            evals = []
            for namespace, job_id in sorted(jobs):
                job = snap.job_by_id(job_id, namespace)
                if job is None:
                    continue
                evals.append(Evaluation(
                    id=generate_uuid(), namespace=namespace,
                    priority=job.priority, type=job.type,
                    triggered_by=enums.TRIGGER_PREEMPTION, job_id=job_id,
                    status=enums.EVAL_STATUS_PENDING, create_time=now,
                    modify_time=now))
            span.set(jobs=len(evals), victims=len(taken or ()) + sum(
                len(v) for v in result.node_preemptions.values()))
        REGISTRY.incr("nomad.preempt.evals", len(evals))
        return evals

    def _columnar_verdicts(self, snap, plan: Plan, fresh: List[str],
                           exact: List[str]) -> Tuple[int, set]:
        """The fit re-check of new-placements-only nodes, as arrays in
        the row numbers of the store's dense columns. Every live node
        row of the plan's blocks and every placement on the nodes of
        `fresh` is one (row, usage) pair, and so is what the in-flight
        results add; committed usage and open capacity of the plan's
        rows are one gather each at the snapshot's generation; all
        pairs are summed a node by one indexed add into a sparse
        accumulator and read back at the plan's rows; one float64
        comparison a dimension gives the verdicts. A block's row on a
        node of `exact` gets none here: _node_plan_valid judges that
        node, the block's placements on it. The port numbers and device
        instances of a block that carries them get their verdict beside
        the fit's (_block_id_verdicts), after the gathers. -> (nodes
        given a verdict, ids of those it went against); a node that is
        down, draining or gone is one."""
        cols = snap.node_columns()
        blocks = [_BlockRows.of(cols, block) for block in plan.alloc_blocks]
        ids = [nid for nid in fresh for _ in plan.node_allocation[nid]]
        if not blocks and not ids:
            return 0, set()
        inflight = getattr(snap, "inflight", None)
        others, other_rows = inflight(cols) if inflight else ([], None)
        if (len(blocks) == 1 and not ids and not exact and other_rows is None
                and not plan.preempted and blocks[0].distinct
                and all(blocks[0].row_set.isdisjoint(o.row_set)
                        for o in others)):
            # one block on nodes of its own that nothing in flight
            # touches, the common case: what it asks is all that is
            # asked of them, and its node ids are read only if a
            # verdict went against one
            own = blocks[0]
            used, avail = cols.read(own.rows)
            ok = _fits(used, lambda d: own.counts * own.vec[d], avail)
            bad = self._block_id_verdicts(snap, plan, exact)
            if ok.all():
                return own.n_nodes, bad
            every = plan.alloc_blocks[0].live_node_counts()[0]
            return own.n_nodes, bad.union(
                itertools.compress(every, (~ok).tolist()))
        pairs = [(b.rows, b.usage()) for b in blocks]
        if ids:
            pairs.append((cols.rows(ids), np.asarray(
                [a.allocated_vec for nid in fresh
                 for a in plan.node_allocation[nid]])))
        rows = (pairs[0][0] if len(pairs) == 1
                else np.concatenate([r for r, _ in pairs]))
        # the committed side first, while the store still stands where
        # the snapshot was taken
        used, avail = cols.read(rows)
        pairs += [(o.rows, o.usage()) for o in others]
        if other_rows is not None:
            pairs.append(other_rows)
        if plan.preempted:
            # used - victims + asked <= avail: a victim still live at
            # this snapshot, and not stopped or preempted by an
            # in-flight result, frees what it holds
            taken = plan.preempted
            live = snap.live_mask(taken.alloc_ids)
            pairs.append((cols.rows(taken.node_ids)[live],
                          -taken.vecs[live]))
        at = np.concatenate([r for r, _ in pairs])
        acc = self._accumulator(cols.capacity())
        try:
            np.add.at(acc, at, np.concatenate([u for _, u in pairs]))
            asked = acc[rows]
        finally:
            acc[at] = 0.0
        ok = _fits(used, lambda d: asked[:, d], avail)
        every = [nid for block in plan.alloc_blocks
                 for nid in block.live_node_counts()[0]] + ids
        if exact:
            kept = set(exact)
            mine = [nid not in kept for nid in every]
            every = list(itertools.compress(every, mine))
            ok = ok[np.asarray(mine, dtype=bool)]
        return (len(set(every)),
                self._block_id_verdicts(snap, plan, exact).union(
                    itertools.compress(every, (~ok).tolist())))

    def _block_id_verdicts(self, snap, plan: Plan, exact: List[str]) -> set:
        """The port and the device verdicts of the plan's blocks' node
        rows outside `exact`."""
        return (self._block_port_verdicts(snap, plan, exact)
                | self._block_device_verdicts(snap, plan, exact))

    def _block_port_verdicts(self, snap, plan: Plan,
                             exact: List[str]) -> set:
        """The port check of the node rows of the plan's blocks that
        carry ports, on the nodes outside `exact`, by allocs_fit's rule
        (structs/network.py check_port_collisions): the node's
        agent-reserved ports, the numbers its allocations hold in the
        snapshot, in-flight results included (ports_by_node, read where
        _node_plan_valid reads its allocations, without materialising
        one), and the plan's new numbers there are all distinct. A node
        that is down, draining or gone is left to the fit's verdict, as
        _node_plan_valid rejects it before its port check. -> ids of the
        nodes it rejects, each counted as a port collision."""
        asked: Dict[str, list] = {}
        kept = set(exact)
        for block in plan.alloc_blocks:
            if block.ports is None:
                continue
            for m in block.live_rows():
                nid = block.node_ids[m]
                if nid not in kept:
                    values = asked.setdefault(nid, [])
                    for _, row in block.ports_for_row(m):
                        values.extend(row)
        bad = set()
        for nid, values in asked.items():
            node = snap.node_by_id(nid)
            if (node is None or node.status != enums.NODE_STATUS_READY
                    or node.drain):
                continue
            held = snap.ports_by_node(nid)
            taken = set(node.reserved.reserved_ports)
            n = len(taken) + len(held) + len(values)
            taken.update(held)
            taken.update(values)
            if len(taken) != n:
                bad.add(nid)
        self._count_port_collisions(len(bad))
        return bad

    def _block_device_verdicts(self, snap, plan: Plan,
                               exact: List[str]) -> set:
        """The device check of the node rows of the plan's blocks that
        carry instances, on the nodes outside `exact`, by allocs_fit's
        rule (structs/funcs.py): no instance of the node held twice
        among its allocations in the snapshot, in-flight results
        included (devices_by_node, read where _node_plan_valid reads its
        allocations, without materialising one), and the plan's new
        instances there; and no new instance that is not the node's
        (foreign_devices). A node that is down, draining or gone is left
        to the fit's verdict, as _node_plan_valid rejects it before its
        device check. -> ids of the nodes it rejects, each counted as a
        device collision."""
        asked: Dict[str, list] = {}
        kept = set(exact)
        for block in plan.alloc_blocks:
            if block.devices is None:
                continue
            for m in block.live_rows():
                nid = block.node_ids[m]
                if nid not in kept:
                    pairs = asked.setdefault(nid, [])
                    for _, held in block.devices_for_row(m):
                        pairs.extend(held)
        bad = set()
        for nid, pairs in asked.items():
            node = snap.node_by_id(nid)
            if (node is None or node.status != enums.NODE_STATUS_READY
                    or node.drain):
                continue
            own = node_instances(node)
            if not own.issuperset(pairs):
                bad.add(nid)
                continue
            held = [x for x in snap.devices_by_node(nid) if x in own]
            held.extend(pairs)
            if len(set(held)) != len(held):
                bad.add(nid)
        self._count_device_collisions(len(bad))
        return bad

    def _count_device_collisions(self, n: int) -> None:
        """Node rows rejected for a device instance held twice, or a new
        one not the node's: what the in-flight overlay's instances exist
        to prevent (scheduler/devices.py), counted as port collisions
        are."""
        if not n:
            return
        from .metrics import REGISTRY

        with self._stats_lock:
            self.stats["device_collisions"] += n
        REGISTRY.incr("nomad.plan.device_collisions", n)

    def _count_port_collisions(self, n: int) -> None:
        """Node rows rejected for a port number handed out twice: the
        one reason of a rejected row that is kept, which the in-flight
        overlay exists to prevent (structs/network.py); a verdict given
        again on a re-verified plan counts again, as in nodes_verified."""
        if not n:
            return
        from .metrics import REGISTRY

        with self._stats_lock:
            self.stats["port_collisions"] += n
        REGISTRY.incr("nomad.plan.port_collisions", n)

    def _accumulator(self, n_rows: int) -> np.ndarray:
        """This thread's (n_rows, D) float64 zeros, for summing usage a
        node by row number: whoever writes on it puts the zeros back."""
        acc = getattr(self._scratch, "acc", None)
        if acc is None or acc.shape[0] < n_rows:
            acc = self._scratch.acc = np.zeros((n_rows, RESOURCE_DIMS))
        return acc

    def _volume_rejections(self, snap, plan: Plan) -> set:
        """Cross-node claim re-verification for csi-volume placements:
        writer exclusivity is a per-VOLUME invariant, so it can't live in
        the per-node check. Counts each volume's existing writers plus
        the plan's new writer claims (racing plans may have claimed
        since the scheduler's snapshot) and rejects the nodes whose
        placements would overcommit (reference volume claim transaction,
        nomad/csi_endpoint.go claim path)."""
        from ..structs.volumes import (MULTI_WRITER_MODES, csi_writer_sources,
                                       live_blocking_writers)

        # (ns, source) -> [(node_id, job_id)] of NEW write placements
        writers_wanted: Dict[tuple, List[tuple]] = {}
        for node_id, allocs in plan.node_allocation.items():
            for a in allocs:
                if snap.alloc_by_id(a.id) is not None:
                    continue  # updates keep their claims
                for key in csi_writer_sources(a):
                    writers_wanted.setdefault(key, []).append(
                        (node_id, a.job_id))
        bad: set = set()
        pending = (snap.overlay_writer_volumes()
                   if hasattr(snap, "overlay_writer_volumes") else set())
        for (ns, source), wants in writers_wanted.items():
            vol = (snap.volume_by_id(source, ns)
                   if hasattr(snap, "volume_by_id") else None)
            if vol is None:
                bad.update(n for n, _ in wants)  # volume vanished
                continue
            if vol.access_mode in MULTI_WRITER_MODES:
                continue
            # claims of allocs this plan stops are being released; any
            # other live claim (a racing job or a live sibling) blocks
            taken = (bool(live_blocking_writers(vol, snap, plan))
                     or (ns, source) in pending)
            free = 0 if taken else 1
            for node_id, _ in sorted(wants):  # deterministic winner
                if free > 0:
                    free -= 1
                else:
                    bad.add(node_id)
        return bad

    def _node_plan_valid(self, snap, plan: Plan, node_id: str) -> bool:
        node = snap.node_by_id(node_id)
        all_allocation = plan.node_allocation.get(node_id, [])
        if plan.alloc_blocks:
            block_allocs = plan.block_allocs_for_node(node_id)
            if block_allocs:
                all_allocation = list(all_allocation) + block_allocs
        if not all_allocation:
            # stops and preemptions only: nothing is placed or updated
            # here, so there is nothing to fit (the checks below all
            # pass on an empty `placements`) and no reason to read the
            # node's allocations, each block position of them a row
            return True
        # classify placement-vs-update by id-existence on the node including
        # client-terminal allocs: a follow_up_eval_id annotation on a failed
        # alloc is an update, not a new placement
        all_node = snap.allocs_by_node(node_id)
        existing = [a for a in all_node if not a.terminal_status()]
        existing_ids = {a.id for a in all_node}
        # node_allocation carries both NEW placements and updates to
        # existing allocs (unknown-marking, follow-up annotations); only
        # new placements require a ready node — updates must land even on
        # down/disconnected/draining nodes (plan_apply.go:789-812)
        placements = [a for a in all_allocation if a.id not in existing_ids]
        if node is None:
            # stops/preemptions/updates against a vanished node are fine;
            # new placements are not
            return not placements
        if placements and (node.status != enums.NODE_STATUS_READY or node.drain):
            return False
        if not placements:
            return True

        removed = {a.id for a in plan.node_update.get(node_id, ())}
        removed |= {a.id for a in plan.node_preemptions.get(node_id, ())}
        if plan.preempted:
            removed.update(plan.preempted.on_node(node_id))
        proposed = [a for a in existing if a.id not in removed]
        updated_ids = {a.id for a in all_allocation}
        proposed = [a for a in proposed if a.id not in updated_ids]
        proposed.extend(all_allocation)

        check_devices = any(a.allocated_devices for a in proposed)
        fit, dim, _ = allocs_fit(node, proposed, check_devices=check_devices)
        if fit and check_devices and foreign_devices(node, placements):
            fit, dim = False, "device oversubscribed"
        if not fit and dim.startswith("port collision"):
            self._count_port_collisions(1)
        elif not fit and dim == "device oversubscribed":
            self._count_device_collisions(1)
        return fit
