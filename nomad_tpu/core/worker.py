"""Scheduler workers (reference nomad/worker.go, 905 LoC).

Each worker loops: dequeue evals from the broker, wait for the state
store to reach the eval's modify index (worker.go:591 snapshotMinIndex),
instantiate the right scheduler against that immutable snapshot, run it,
and ack/nack. The worker is also the scheduler's Planner: plan submission
routes through the leader plan queue and blocks on the applier's verdict
(worker.go:650 SubmitPlan); partial commits hand back a fresher snapshot
so the scheduler retries in-process.

Batched mode (ServerConfig.eval_batch_size > 1): the worker drains up to
K ready evals in one dequeue, acquires ONE snapshot at the batch's max
modify index, and runs the members concurrently on a small per-worker
pool. Each member's plan commit and final eval-status write then overlap
with its siblings', so the plan applier's commit thread coalesces the
whole batch — up to workers x K commits — into one replicated round
instead of one round per eval. Per-eval state lives in an _EvalRun, so
concurrent members never share mutable scheduler state; per-job
serialization is the broker's (a batch never holds two evals of one job).
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

from ..obs import TRACER
from ..scheduler.scheduler import NewScheduler
from ..structs import enums
from ..structs.evaluation import Evaluation
from ..structs.plan import Plan

ALL_SCHED_TYPES = [
    enums.JOB_TYPE_SERVICE, enums.JOB_TYPE_BATCH,
    enums.JOB_TYPE_SYSTEM, enums.JOB_TYPE_SYSBATCH,
]


class _EvalRun:
    """One eval's processing state + its Planner implementation.

    Confined to the single thread executing run() (the worker loop or
    one of the worker's batch-pool threads); nothing here is shared,
    which is what lets batch members run concurrently.
    """

    def __init__(self, worker: "Worker", ev: Evaluation, token: str,
                 snapshot=None):
        self.worker = worker
        self.server = worker.server
        self.ev = ev
        self.token = token
        self.snapshot = snapshot

    def run(self):
        """Process the eval; ack on success (after every status write
        is durably committed), nack on failure. Returns the snapshot
        the eval ended on (possibly refreshed by a partial commit) so a
        serial caller can carry it forward, or None on failure."""
        ev, server = self.ev, self.server
        try:
            # every span this thread opens for this eval (snapshot,
            # schedule, plan.submit, eval.persist, solver waits deeper
            # down) inherits the eval's trace id from the bind
            with TRACER.bind(ev.trace()):
                snap = self.snapshot
                if snap is None or snap.index < ev.modify_index:
                    with TRACER.span("worker.snapshot",
                                     index=ev.modify_index):
                        snap = server.store.snapshot_min_index(
                            ev.modify_index)
                self.snapshot = snap
                sched = NewScheduler(
                    ev.type, snap, self,
                    sched_config=server.sched_config,
                    logger=server.logger,
                    shared_caches=self.worker._sched_caches,
                    on_event=lambda e: server.events.publish(
                        "Scheduler", e.get("type", "scheduler-event"), e))
                from .metrics import REGISTRY

                with REGISTRY.time(
                        f"nomad.worker.invoke_scheduler_{ev.type}"), \
                        TRACER.span("worker.schedule", type=ev.type):
                    sched.process(ev)
                server.broker.ack(ev.id, self.token)
            self.worker._count("processed")
            return self.snapshot
        except Exception:
            if server.logger:
                server.logger.exception("eval %s failed", ev.id)
            self.worker._count("nacked")
            try:
                server.broker.nack(ev.id, self.token)
            except ValueError:
                pass  # nack timer already fired
            return None

    # -- Planner interface (worker.go:650-802) --

    def submit_plan(self, plan: Plan):
        plan.snapshot_index = getattr(self.snapshot, "index", 0) or 0
        with TRACER.span("plan.submit"):
            pending = self.server.plan_queue.enqueue(plan)
            # Generous (queue depth spikes when every worker submits a
            # large plan at once) but bounded well inside the broker's
            # nack timer — waiting the full nack window guarantees
            # redelivery of an eval that is still being processed.
            result = pending.wait(
                timeout=max(10.0, self.server.config.nack_timeout / 2.0))
        if result.refresh_index:
            # partial commit: hand the scheduler a fresher snapshot
            new_snap = self.server.store.snapshot_min_index(result.refresh_index)
            self.snapshot = new_snap
            return result, new_snap
        return result, None

    def _persist_eval(self, ev: Evaluation) -> None:
        """Durably commit one eval's status before acting on it. The
        write rides the plan-commit batch — one replicated round shared
        with every plan and eval update concurrently waiting at the
        commit thread — and blocks until that round lands, preserving
        the direct write's durability-before-ack semantics exactly."""
        with TRACER.span("eval.persist"):
            try:
                fut = self.server.plan_applier.submit_eval_updates([ev])
            except RuntimeError:
                # applier already stopped (leadership lost mid-eval):
                # fall through to the direct write, which surfaces
                # the real not-leader error to run()'s nack path
                self.server.store.upsert_evals([ev])
                return
            fut.result(timeout=max(
                10.0, self.server.config.nack_timeout / 2.0))

    def update_eval(self, ev: Evaluation) -> None:
        self._persist_eval(ev)
        if ev.should_block():
            self.server.blocked.block(ev)

    def create_eval(self, ev: Evaluation) -> None:
        self._persist_eval(ev)
        if ev.should_block():
            self.server.blocked.block(ev)
        elif ev.should_enqueue():
            self.server.broker.enqueue(ev)

    def reblock_eval(self, ev: Evaluation) -> None:
        self._persist_eval(ev)
        self.server.blocked.block(ev)


class Worker:
    def __init__(self, server, worker_id: int = 0,
                 sched_types: Optional[List[str]] = None):
        self.server = server
        self.id = worker_id
        self.sched_types = sched_types or list(ALL_SCHED_TYPES)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.stats = {"processed": 0, "nacked": 0}
        self._stats_lock = threading.Lock()
        # batch-member pool (created at start)
        self._batch_pool: Optional[ThreadPoolExecutor] = None
        # the still-settling previous batch: (futures, publish_delta).
        # process_batch leaves a batch draining on the pool and returns
        # to the dequeue loop, so the NEXT batch's solves reach the
        # solver service while these members plan-verify/commit — the
        # worker half of the solve/apply double buffer
        self._prev_batch = None
        # cross-eval constraint caches (regex compiles, parsed versions):
        # content-keyed with immutable values, so the worst concurrent
        # access from batch-pool members is a benign duplicate compile
        # (dict get/set are single GIL-atomic ops)
        self._sched_caches: dict = {}

    def _count(self, key: str) -> None:
        with self._stats_lock:
            self.stats[key] += 1

    # -- lifecycle --

    def start(self) -> None:
        self._stop.clear()
        if self._batch_pool is None:
            # 2x: one batch plan-applying + one batch solving at any
            # moment (the double buffer) — a pool sized at batch_size
            # would make the fresh batch's rendezvous wait out the
            # previous batch's commits thread-by-thread
            self._batch_pool = ThreadPoolExecutor(
                max_workers=2 * self.server.config.eval_batch_size,
                thread_name_prefix=f"worker-{self.id}-eval")
        self._thread = threading.Thread(target=self.run, daemon=True,
                                        name=f"worker-{self.id}")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._batch_pool is not None:
            self._batch_pool.shutdown(wait=False)
            self._batch_pool = None

    def join(self, timeout: float = 2.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    # -- the loop (worker.go:397 run) --

    def run(self) -> None:
        while not self._stop.is_set():
            batch = self.server.broker.dequeue_batch(
                self.sched_types,
                max_batch=self.server.config.eval_batch_size, timeout=0.2)
            if not batch:
                # idle: settle the deferred batch so its ack/nack
                # and stats publish promptly
                self._drain_prev()
                continue
            self.process_batch(batch)
        self._drain_prev()

    def _drain_prev(self) -> None:
        """Block until the deferred previous batch finishes and publish
        its preemption split. Runs on the worker thread only."""
        prev = self._prev_batch
        if prev is None:
            return
        self._prev_batch = None  # san-ok: confined to the run-loop thread
        futs, publish = prev
        for f in futs:
            try:
                f.result()
            except Exception:
                pass  # _EvalRun.run never raises; belt and braces
        publish()

    def process_batch(self, batch: List) -> None:
        """Run a drained batch of evals against ONE shared snapshot:
        snapshot_min_index is paid once for the whole batch (at the max
        member index), and every scheduler in the batch reuses the
        store-cached ClusterStatic for that node-set version — the
        per-eval constant costs that dominate small evals. Members run
        concurrently on the worker's pool, so their plan commits and
        status writes coalesce at the applier's commit thread. Members
        still ack/nack individually; a failure redelivers that eval
        alone."""
        from .metrics import REGISTRY
        from ..tensor import incremental
        from ..tensor.placer import preempt_stats

        REGISTRY.set_gauge("nomad.worker.eval_batch_size", len(batch))
        # per-batch preemption-path split: how much of this batch's
        # preemption resolved in-kernel vs through the exact host
        # scanner (the nomad.preempt.* counters are cumulative; the
        # delta across one batch is what the obs plane graphs)
        preempt_before = preempt_stats()
        # per-batch tensor-build route split: warm builds served O(Δ)
        # off the incremental device state vs cold full rebuilds
        # (resyncs) — the nomadstate feed's counters are cumulative
        state_before = incremental.GLOBAL.stats()
        snap = None
        try:
            target = max(ev.modify_index for ev, _ in batch)
            # batch-shared span: one snapshot serves every member, so
            # the span lists all their traces instead of claiming one
            with TRACER.span("worker.snapshot", index=target,
                             traces=[ev.trace() for ev, _ in batch]):
                snap = self.server.store.snapshot_min_index(target)
        except Exception:
            snap = None  # fall back to per-eval acquisition
        def publish_preempt_delta():
            post = preempt_stats()
            for key in ("kernel_preempted", "host_preempted"):
                delta = post[key] - preempt_before[key]
                if delta:
                    REGISTRY.set_gauge(f"nomad.worker.batch_{key}", delta)
            state_post = incremental.GLOBAL.stats()
            fast = state_post["fast_hits"] - state_before["fast_hits"]
            full = ((state_post["builds"] - state_before["builds"]) - fast)
            if fast or full:
                REGISTRY.set_gauge("nomad.worker.batch_state_fast_builds",
                                   fast)
                REGISTRY.set_gauge("nomad.worker.batch_state_full_builds",
                                   full)

        pool = self._batch_pool
        if len(batch) == 1 or pool is None:
            self._drain_prev()  # the inline path stays strictly ordered
            for ev, token in batch:
                if self._stop.is_set():
                    # shutting down: leave the rest to the nack timers
                    break
                # a partial commit inside a previous member refreshed
                # the snapshot; carry the fresher one forward
                snap = self.process_one(ev, token, snapshot=snap) or snap
            publish_preempt_delta()
            return
        # "tpu-solve": open a rendezvous sized to this dequeue_batch so
        # the bulk-solver service coalesces every member's solve into
        # ONE joint auction launch (tensor/batch_solver.py). Each member
        # keeps its own _EvalRun / Plan / ack, so per-job plan
        # boundaries and broker serialization are untouched — the
        # rendezvous only shapes WHEN the device launch fires.
        batch_ctx = None
        sched_config = getattr(self.server, "sched_config", None)
        if (sched_config is not None and sched_config.scheduler_algorithm
                == enums.SCHED_ALG_TPU_SOLVE):
            from ..tensor.solver import open_batch

            batch_ctx = open_batch(len(batch))
        futs = []
        try:
            for ev, token in batch:
                futs.append(pool.submit(
                    self._run_member, batch_ctx,
                    _EvalRun(self, ev, token, snapshot=snap)))
        except RuntimeError:
            # pool shut down mid-batch: unsubmitted members redeliver
            # via their nack timers; settle them so the solver service
            # doesn't hold the launch for members that never ran
            if batch_ctx is not None:
                for _ in range(len(batch) - len(futs)):
                    batch_ctx.settle()
        # double buffer: drain the PREVIOUS batch (its members ran while
        # this one was dequeued, snapshotted, and submitted), then leave
        # THIS batch settling on the pool — the dequeue loop goes
        # straight back to the broker, and the next batch's solves reach
        # the solver service while these members plan-verify/commit.
        # Each member still acks/nacks its own eval, so at most two
        # batches in flight is indistinguishable from two workers.
        self._drain_prev()
        # san-ok: confined to the run-loop thread (only run() reaches here)
        self._prev_batch = (futs, publish_preempt_delta)

    @staticmethod
    def _run_member(batch_ctx, eval_run):
        if batch_ctx is None:
            return eval_run.run()
        from ..tensor.solver import batch_member

        with batch_member(batch_ctx):
            return eval_run.run()

    def process_one(self, ev: Evaluation, token: str, snapshot=None):
        """Process a single eval inline on the calling thread."""
        return _EvalRun(self, ev, token, snapshot=snapshot).run()
