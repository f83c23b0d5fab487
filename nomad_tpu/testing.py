"""In-process scheduler test harness (reference scheduler/testing.go:51).

A real state store + a fake Planner that applies plans directly, so every
scheduler behavior is testable single-process — the reference's key
testing insight (SURVEY.md §4).
"""

from __future__ import annotations

import threading
from typing import List, Optional

from .scheduler.scheduler import NewScheduler
from .state import StateStore
from .structs import enums
from .structs.evaluation import Evaluation
from .structs.plan import Plan, PlanResult


class Harness:
    def __init__(self, store: Optional[StateStore] = None):
        self.store = store if store is not None else StateStore()
        self.plans: List[Plan] = []
        self.evals: List[Evaluation] = []
        self.created_evals: List[Evaluation] = []
        self.reblocked_evals: List[Evaluation] = []
        self.reject_plan = False     # reference testing.go:22 RejectPlan
        self.reject_once = False
        self._lock = threading.Lock()

    # -- Planner interface (reference testing.go:93-185) --

    def submit_plan(self, plan: Plan):
        with self._lock:
            self.plans.append(plan)
            if self.reject_plan:
                if self.reject_once:
                    self.reject_plan = False
                result = PlanResult(refresh_index=self.store.latest_index)
                # nothing committed: every planned node counts as
                # rejected so solver-ledger hooks correct their usage
                nodes = set(plan.node_allocation)
                for b in plan.alloc_blocks:
                    nodes.update(b.node_ids)
                result.rejected_nodes = sorted(nodes)
                self._run_hooks(plan, result)
                return result, self.store.snapshot()

            placements, stops, preemptions = [], [], []
            for allocs in plan.node_allocation.values():
                placements.extend(allocs)
            for allocs in plan.node_update.values():
                stops.extend(allocs)
            for allocs in plan.node_preemptions.values():
                preemptions.extend(allocs)
            index = self.store.upsert_plan_results(
                placements, stopped_allocs=stops, preempted_allocs=preemptions,
                deployment=plan.deployment,
                deployment_updates=plan.deployment_updates,
                evals=list(plan.eval_updates),
                alloc_blocks=list(plan.alloc_blocks),
                preempted_positions=plan.preempted,
            )
            result = PlanResult(
                node_allocation=plan.node_allocation,
                node_update=plan.node_update,
                node_preemptions=plan.node_preemptions,
                alloc_blocks=list(plan.alloc_blocks),
                preempted=plan.preempted,
                alloc_index=index,
            )
            self._run_hooks(plan, result)
            return result, None

    @staticmethod
    def _run_hooks(plan: Plan, result: PlanResult) -> None:
        """Planner contract: post-apply hooks fire synchronously with
        the commit (see core/plan_apply.py _commit)."""
        for hook in plan.post_apply_hooks:
            try:
                hook(result)
            except Exception:
                pass

    def update_eval(self, evaluation: Evaluation) -> None:
        with self._lock:
            self.evals.append(evaluation)

    def create_eval(self, evaluation: Evaluation) -> None:
        with self._lock:
            self.created_evals.append(evaluation)

    def reblock_eval(self, evaluation: Evaluation) -> None:
        with self._lock:
            self.reblocked_evals.append(evaluation)

    # -- helpers --

    def snapshot(self):
        return self.store.snapshot()

    def process(self, evaluation: Evaluation, sched_config=None, placer=None) -> None:
        """Instantiate the right scheduler and process one eval
        (reference testing.go:296 Process)."""
        sched = NewScheduler(evaluation.type, self.store.snapshot(), self,
                             sched_config=sched_config, placer=placer)
        sched.process(evaluation)

    def assert_eval_status(self, expected: str) -> Evaluation:
        assert self.evals, "no eval updates recorded"
        last = self.evals[-1]
        assert last.status == expected, (
            f"eval status {last.status!r} (desc {last.status_description!r}), "
            f"want {expected!r}")
        return last
