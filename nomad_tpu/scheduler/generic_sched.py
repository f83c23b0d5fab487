"""Service + batch scheduler (reference scheduler/generic_sched.go, 945 LoC).

Retry loop: reconcile -> place -> submit plan -> on partial commit refresh
snapshot and retry (<=5 attempts service / 2 batch); unplaceable allocs
produce/refresh a blocked evaluation (reference generic_sched.go:149-356).
"""

from __future__ import annotations

import copy as _copy
import time
from typing import List, Optional

from ..obs import TRACER
from ..structs import enums
from ..structs.alloc import Allocation, RescheduleEvent, RescheduleTracker
from ..structs.evaluation import Evaluation
from ..utils import generate_uuid
from .context import EvalContext
from .placer import HostPlacer, placer_for_algorithm
from .reconcile import AllocReconciler, PlacementRequest
from .util import tainted_nodes, update_non_terminal_allocs_to_lost

MAX_SERVICE_ATTEMPTS = 5  # reference generic_sched.go:94
MAX_BATCH_ATTEMPTS = 2

BLOCKED_EVAL_MAX_PLAN_DESC = "created due to placement conflicts"
BLOCKED_EVAL_FAILED_PLACEMENT_DESC = "created to place remaining allocations"


class GenericScheduler:
    def __init__(self, state, planner, *, batch: bool = False,
                 sched_config=None, logger=None, placer=None, on_event=None,
                 shared_caches=None):
        self.state = state            # a StateSnapshot-like view
        self.planner = planner
        self.batch = batch
        self.sched_config = sched_config
        self.logger = logger
        self.on_event = on_event
        # cross-eval constraint caches (see NewScheduler); None = per-eval
        self.shared_caches = shared_caches
        algorithm = (sched_config.scheduler_algorithm
                     if sched_config is not None else enums.SCHED_ALG_BINPACK)
        self._placer_injected = placer is not None
        self._base_algorithm = algorithm
        self.placer = placer if placer is not None else placer_for_algorithm(algorithm)
        self.max_attempts = MAX_BATCH_ATTEMPTS if batch else MAX_SERVICE_ATTEMPTS

        self.eval: Optional[Evaluation] = None
        self.plan = None
        self.deployment = None
        self.failed_tg_allocs = {}
        self.queued_allocs = {}
        self.blocked: Optional[Evaluation] = None
        self.followups: List[Evaluation] = []

    # -- Scheduler interface --

    def process(self, evaluation: Evaluation) -> None:
        self.eval = evaluation
        try:
            self._process_with_retries()
        except Exception as e:  # reference recovers panics into failed evals
            if self.logger:
                self.logger.exception("scheduler panic")
            self._set_status(enums.EVAL_STATUS_FAILED, str(e))
            raise

    # -- core loop --

    def _process_with_retries(self) -> None:
        # the attempt budget only counts *zero-progress* retries: a partial
        # commit resets it (reference scheduler/util.go retryMax's
        # progressMade callback, generic_sched.go:149) — under worker
        # contention every plan can be partially rejected many times in a
        # row while still converging, and that must not exhaust the eval
        attempt = 0
        fruitless = 0
        while fruitless < self.max_attempts:
            self._progress = False
            if self._attempt(attempt):
                return
            attempt += 1
            fruitless = 0 if self._progress else fruitless + 1
        # exceeded plan attempts: fail this eval but queue a blocked eval
        # so the work is not lost (reference generic_sched.go:151-170)
        self._create_blocked_eval(max_plan=True)
        self._set_status(enums.EVAL_STATUS_FAILED, "maximum attempts reached")

    def _attempt(self, attempt: int) -> bool:
        ev = self.eval
        self.failed_tg_allocs = {}
        self.queued_allocs = {}
        self.followups = []
        job = self.state.job_by_id(ev.job_id, ev.namespace)
        self.plan = ev.make_plan(job)
        ctx = EvalContext(self.state, self.plan, eval_id=ev.id, logger=self.logger,
                          on_event=self.on_event)
        if self.shared_caches is not None:
            ctx.regex_cache = self.shared_caches.setdefault("regex", {})
            ctx.version_cache = self.shared_caches.setdefault("version", {})
        if job is not None:
            ctx.eligibility.set_job(job)

        # span worker.reconcile (arg `places`): the job's allocations,
        # the tainted nodes and the reconciler's diff
        with TRACER.span("worker.reconcile") as span:
            all_allocs = self.state.allocs_by_job(ev.job_id, ev.namespace)
            tainted = tainted_nodes(self.state, all_allocs)
            update_non_terminal_allocs_to_lost(self.plan, tainted, all_allocs)

            latest_dep = (
                self.state.latest_deployment_by_job(ev.job_id, ev.namespace)
                if not self.batch else None)
            reconciler = AllocReconciler(
                job if (job is not None and not job.stopped()) else None,
                ev.job_id, all_allocs, tainted, batch=self.batch,
                eval_id=ev.id, deployment=latest_dep)
            results = reconciler.compute()
            span.set(places=results.total_places())
        # per-TG desired-update annotations, surfaced by the dry-run plan
        # endpoint (reference scheduler/annotate.go:42 Annotate)
        self.annotations = dict(results.desired_tg_updates)

        # deployments track service-job rollouts (reference reconcile.go
        # computeDeployments; watched by nomad/deploymentwatcher). A new
        # job version with an update stanza opens a new deployment.
        self.deployment = None
        if not self.batch and job is not None and not job.stopped():
            latest = self.state.latest_deployment_by_job(ev.job_id, ev.namespace)
            has_update = any(tg.update is not None for tg in job.task_groups)
            changes = results.total_places() > 0
            # a new deployment only for a job version that never had one —
            # a terminal deployment for the current version must NOT be
            # re-opened by later placements (drains, reschedules), or a
            # plain node drain could stall-fail-and-revert the job
            if has_update and changes and (
                    latest is None or latest.job_version != job.version):
                from ..structs.deployment import Deployment, DeploymentState

                dep = Deployment(
                    id=generate_uuid(),
                    namespace=job.namespace,
                    job_id=job.id,
                    job_version=job.version,
                    eval_priority=ev.priority,
                )
                now0 = time.time()
                for tg in job.task_groups:
                    if tg.update is None:
                        continue
                    # groups whose update is entirely in-place (or a
                    # no-op) have nothing to health-track; a deployment
                    # state for them would sit at 0 placements until the
                    # progress deadline failed it
                    tgr = results.groups.get(tg.name)
                    if tgr is None or not (tgr.place or tgr.destructive_update):
                        continue
                    # canaries only apply to UPDATE rollouts: the deployment
                    # demands canaries iff the reconciler actually asked for
                    # canary placements this eval. Initial versions and
                    # rollouts whose old allocs are all lost (replaced
                    # outright) must not, or the canary hold would fire on
                    # every later eval and stall a fully-placed rollout
                    # (reference reconcile.go requireCanary)
                    wants_canaries = any(p.canary for p in tgr.place)
                    dep.task_groups[tg.name] = DeploymentState(
                        auto_revert=tg.update.auto_revert,
                        auto_promote=tg.update.auto_promote,
                        desired_canaries=tg.update.canary if wants_canaries else 0,
                        desired_total=tg.count,
                        progress_deadline_s=tg.update.progress_deadline_s,
                        require_progress_by=now0 + tg.update.progress_deadline_s,
                    )
                if dep.task_groups:
                    self.deployment = dep
                    self.plan.deployment = dep
            elif latest is not None and latest.active() \
                    and latest.job_version == job.version:
                self.deployment = latest

        # plan stops
        for tg_name, g in results.groups.items():
            for alloc, desc, client_status in g.stop:
                self.plan.append_stopped_alloc(alloc, desc, client_status)
            for alloc in g.destructive_update:
                self.plan.append_stopped_alloc(
                    alloc, "alloc is being updated due to job update")
            # in-place updates: same alloc, same node, same resources —
            # only the job definition it runs under advances (reference
            # scheduler/util.go genericAllocUpdateFn's in-place arm).
            # They join the active deployment so a mixed in-place/
            # destructive rollout can still reach the watcher's
            # "desired_total tracked allocs" completion bar; their
            # carried health keeps counting.
            tg_obj = job.lookup_task_group(tg_name) if job else None
            for alloc in g.inplace_update:
                upd = alloc.copy_for_update()
                upd.job = job
                upd.job_version = job.version
                if (self.deployment is not None and tg_obj is not None
                        and tg_obj.update is not None
                        and tg_name in self.deployment.task_groups):
                    upd.deployment_id = self.deployment.id
                self.plan.node_allocation.setdefault(
                    upd.node_id, []).append(upd)
            self.followups.extend(g.followup_evals)
            # annotate failed-then-delayed allocs with their followup eval
            for alloc_id, feval_id in g.delayed_reschedule.items():
                orig = next((a for a in all_allocs if a.id == alloc_id), None)
                if orig is not None:
                    upd = orig.copy_for_update()
                    upd.follow_up_eval_id = feval_id
                    self.plan.node_allocation.setdefault(upd.node_id, []).append(upd)
            # disconnecting allocs go client=unknown in the plan, tagged
            # with their max-disconnect-timeout eval (reference
            # plan AppendUnknownAlloc; reconcile.go disconnect updates)
            for alloc in g.disconnecting:
                upd = alloc.copy_for_update()
                upd.client_status = enums.ALLOC_CLIENT_UNKNOWN
                upd.client_description = "client disconnected"
                upd.follow_up_eval_id = g.disconnect_updates.get(alloc.id, "")
                self.plan.node_allocation.setdefault(upd.node_id, []).append(upd)

        # build placement request list (destructive updates also re-place)
        requests: List[PlacementRequest] = []
        job_obj = job
        for tg_name, g in results.groups.items():
            tg = job_obj.lookup_task_group(tg_name) if job_obj else None
            for alloc in g.destructive_update:
                requests.append(PlacementRequest(
                    name=alloc.name, task_group=tg, previous_alloc=alloc))
            requests.extend(g.place)
            if g.bulk_place is not None:
                requests.append(g.bulk_place)

        if requests and job_obj is not None:
            self._compute_placements(ctx, job_obj, requests, attempt)

        # no-op plan with nothing failed: done
        if self.plan.is_no_op() and not self.failed_tg_allocs:
            self._finish_success()
            return True

        # submit; the planner runs plan.post_apply_hooks synchronously
        # with its commit (core/plan_apply.py _commit, testing.py
        # Harness.submit_plan) so the solver-service ledger closes in
        # lockstep with the store write
        result, new_state = self.planner.submit_plan(self.plan)
        self._progress = bool(result.node_allocation or result.node_update
                              or result.node_preemptions or result.alloc_blocks
                              or result.deployment is not None)
        if new_state is not None:
            # partial commit: retry against fresher state
            self.state = new_state
            full, expected, actual = result.full_commit(self.plan)
            if not full:
                return False

        self._finish_success()
        return True

    def _compute_placements(self, ctx: EvalContext, job, requests, attempt: int) -> None:
        ev = self.eval
        nodes = self.state.ready_nodes_in_pool(job.datacenters, job.node_pool)
        # per-node-pool scheduler-config overrides (reference
        # generic_sched.go:737-752 applying SchedulerConfig.WithNodePool)
        effective = self.sched_config
        placer = self.placer
        if effective is not None:
            pool_fn = getattr(self.state, "node_pool", None)
            pool = pool_fn(job.node_pool) if pool_fn is not None else None
            effective = effective.with_node_pool(pool)
            if (not self._placer_injected
                    and effective.scheduler_algorithm != self._base_algorithm):
                placer = placer_for_algorithm(effective.scheduler_algorithm)
        preemption_enabled = (
            effective.preemption_enabled_for(job.type)
            if effective is not None else False)

        now = time.time()

        def commit(req, option):
            tg = req.task_group
            if option is None:
                # failed placement: coalesce per task group
                m = ctx.metrics
                prev = self.failed_tg_allocs.get(tg.name)
                if prev is None:
                    self.failed_tg_allocs[tg.name] = m
                else:
                    prev.coalesced_failures += 1
                self.queued_allocs[tg.name] = self.queued_allocs.get(tg.name, 0)
                return

            alloc = Allocation(
                id=generate_uuid(),
                eval_id=ev.id,
                deployment_id=(self.deployment.id
                               if self.deployment is not None
                               and tg.update is not None else ""),
                name=req.name,
                namespace=job.namespace,
                node_id=option.node.id,
                node_name=option.node.name,
                job_id=job.id,
                job=job,
                job_version=job.version,
                task_group=tg.name,
                allocated_vec=ctx.tg_vec(tg),
                allocated_ports=list(option.allocated_ports),
                allocated_devices=dict(option.allocated_devices),
                allocated_cores=list(option.allocated_cores),
                desired_status=enums.ALLOC_DESIRED_RUN,
                client_status=enums.ALLOC_CLIENT_PENDING,
                metrics=ctx.metrics,
                allocated_at=now,
            )
            if req.canary:
                alloc.canary = True
                if self.deployment is not None:
                    # record the placement on a plan-local deployment copy
                    # (the store row is shared MVCC state)
                    if self.plan.deployment is not self.deployment:
                        self.deployment = _copy.deepcopy(self.deployment)
                        self.plan.deployment = self.deployment
                    ds = self.deployment.task_groups.get(tg.name)
                    if ds is not None:
                        ds.placed_canaries = list(ds.placed_canaries) + [alloc.id]
            if req.previous_alloc is not None:
                prev = req.previous_alloc
                alloc.previous_allocation = prev.id
                if req.reschedule:
                    tracker = RescheduleTracker(
                        events=list(prev.reschedule_tracker.events)
                        if prev.reschedule_tracker else [])
                    tracker.events.append(RescheduleEvent(
                        reschedule_time=now, prev_alloc_id=prev.id,
                        prev_node_id=prev.node_id))
                    alloc.reschedule_tracker = tracker
                    # link old -> new
                    upd = prev.copy_for_update()
                    upd.next_allocation = alloc.id
                    self.plan.node_allocation.setdefault(upd.node_id, []).append(upd)
            if option.preempted_allocs:
                for victim in option.preempted_allocs:
                    self.plan.append_preempted_alloc(victim, alloc.id)
            self.plan.append_alloc(alloc)
            self.queued_allocs[tg.name] = self.queued_allocs.get(tg.name, 0) + 1

        def commit_block(tg, node_ids, node_names, counts, name_indices,
                         mean_score, scores=None, nodes_evaluated=0,
                         nodes_in_pool=0, ports=None, devices=None):
            """Columnar bulk commit: ONE AllocBlock rides the plan for K
            placements (structs/alloc.py AllocBlock). Only reachable for
            fresh placements (no canary, no previous allocation, no core
            ids); per-alloc ids/names materialize lazily.
            `scores` (per position) and the two node counts are the
            per-placement scan's: what its rows carried in their
            AllocMetric; `ports` (a BlockPorts, a row a position) its
            port numbers and `devices` (a BlockDevices) its device
            instances, for a group that asks them."""
            from ..structs.alloc import AllocBlock

            block = AllocBlock(
                id=generate_uuid(),
                eval_id=ev.id,
                namespace=job.namespace,
                job_id=job.id,
                job=job,
                job_version=job.version,
                task_group=tg.name,
                deployment_id=(self.deployment.id
                               if self.deployment is not None
                               and tg.update is not None else ""),
                name_indices=name_indices,
                node_ids=list(node_ids),
                node_names=list(node_names),
                counts=counts,
                allocated_vec=ctx.tg_vec(tg),
                mean_score=float(mean_score),
                nodes_evaluated=nodes_evaluated,
                nodes_in_pool=nodes_in_pool,
                ports=ports,
                devices=devices,
                allocated_at=now,
            )
            metrics = ctx.metrics
            if scores is not None:
                block.scores = scores
            elif metrics is not None:
                metrics.scores.setdefault("bulk.normalized-score",
                                          float(mean_score))
            self.plan.append_block(block)
            self.queued_allocs[tg.name] = (
                self.queued_allocs.get(tg.name, 0) + block.size)

        def fail_bulk(tg, n):
            """Coalesced failure accounting for n unplaced bulk requests
            (reference generic_sched.go:563-567 CoalescedFailures)."""
            if n <= 0:
                return
            m = ctx.metrics
            prev = self.failed_tg_allocs.get(tg.name)
            if prev is None:
                m.coalesced_failures += n - 1
                self.failed_tg_allocs[tg.name] = m
            else:
                prev.coalesced_failures += n
            self.queued_allocs.setdefault(tg.name, 0)

        commit.commit_block = commit_block
        commit.fail_bulk = fail_bulk
        placer.place(
            ctx, job, requests, nodes, commit,
            batch=self.batch, preemption_enabled=preemption_enabled,
            attempt=attempt)

    # -- eval bookkeeping --

    def _finish_success(self) -> None:
        for f in self.followups:
            self.planner.create_eval(f)
        if self.failed_tg_allocs:
            self._create_blocked_eval(max_plan=False)
            self._set_status(enums.EVAL_STATUS_COMPLETE,
                             "complete with failed placements")
        else:
            self._set_status(enums.EVAL_STATUS_COMPLETE, "")

    def _create_blocked_eval(self, max_plan: bool) -> None:
        ev = self.eval
        if ev.status == enums.EVAL_STATUS_BLOCKED or ev.triggered_by == enums.TRIGGER_QUEUED_ALLOCS:
            # this eval IS a blocked eval being retried: reblock it
            reblocked = _copy.copy(ev)
            reblocked.status = enums.EVAL_STATUS_BLOCKED
            reblocked.snapshot_index = getattr(self.state, "index", 0) or 0
            self.planner.reblock_eval(reblocked)
            self.blocked = reblocked
            return
        blocked = Evaluation(
            id=generate_uuid(),
            namespace=ev.namespace,
            priority=ev.priority,
            type=ev.type,
            triggered_by=enums.TRIGGER_MAX_PLANS if max_plan else enums.TRIGGER_QUEUED_ALLOCS,
            job_id=ev.job_id,
            status=enums.EVAL_STATUS_BLOCKED,
            status_description=(BLOCKED_EVAL_MAX_PLAN_DESC if max_plan
                                else BLOCKED_EVAL_FAILED_PLACEMENT_DESC),
            previous_eval=ev.id,
            # the snapshot the placements failed on: a capacity change
            # after it was missed (core/blocked.py)
            snapshot_index=getattr(self.state, "index", 0) or 0,
        )
        # class eligibility lets the blocked-evals tracker unblock cheaply
        # (reference generic_sched.go:225 createBlockedEval)
        self.planner.create_eval(blocked)
        self.blocked = blocked

    def _set_status(self, status: str, desc: str) -> None:
        ev = _copy.copy(self.eval)
        ev.status = status
        ev.status_description = desc
        ev.failed_tg_allocs = self.failed_tg_allocs
        ev.queued_allocations = dict(self.queued_allocs)
        if self.blocked is not None:
            ev.blocked_eval = self.blocked.id
        self.planner.update_eval(ev)
