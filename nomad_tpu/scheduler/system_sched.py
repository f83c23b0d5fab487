"""System + sysbatch scheduler (reference scheduler/scheduler_system.go +
system_util.go): place one alloc of each task group on every feasible
node; diff-based, no reconciler.
"""

from __future__ import annotations

import copy as _copy
import time
from typing import Dict, List, Optional, Tuple

from ..structs import enums
from ..structs.alloc import Allocation, alloc_name
from ..structs.evaluation import Evaluation
from ..utils import generate_uuid
from .context import EvalContext
from .rank import NodeScorer, _class_feasible
from .util import tainted_nodes, update_non_terminal_allocs_to_lost


class SystemScheduler:
    def __init__(self, state, planner, *, sysbatch: bool = False,
                 sched_config=None, logger=None, placer=None, on_event=None,
                 shared_caches=None):
        self.state = state
        self.planner = planner
        self.sysbatch = sysbatch
        self.sched_config = sched_config
        self.logger = logger
        self.on_event = on_event
        # the tpu-* placer whose place_system takes a group's fresh
        # nodes in one launch (built on first use from the algorithm)
        self.placer = placer
        # cross-eval constraint caches (see NewScheduler); None = per-eval
        self.shared_caches = shared_caches
        self.eval: Optional[Evaluation] = None
        self.plan = None
        self.failed_tg_allocs = {}
        self.queued_allocs = {}

    def process(self, evaluation: Evaluation) -> None:
        self.eval = evaluation
        for attempt in range(2):
            if self._attempt(attempt):
                return
        self._set_status(enums.EVAL_STATUS_FAILED, "maximum attempts reached")

    def _attempt(self, attempt: int) -> bool:
        ev = self.eval
        self.failed_tg_allocs = {}
        job = self.state.job_by_id(ev.job_id, ev.namespace)
        self.plan = ev.make_plan(job)
        ctx = EvalContext(self.state, self.plan, eval_id=ev.id, logger=self.logger,
                          on_event=self.on_event)
        if self.shared_caches is not None:
            ctx.regex_cache = self.shared_caches.setdefault("regex", {})
            ctx.version_cache = self.shared_caches.setdefault("version", {})

        all_allocs = self.state.allocs_by_job(ev.job_id, ev.namespace)
        tainted = tainted_nodes(self.state, all_allocs)
        update_non_terminal_allocs_to_lost(self.plan, tainted, all_allocs)

        stopped = job is None or job.stopped()
        nodes = ([] if stopped else
                 self.state.ready_nodes_in_pool(job.datacenters, job.node_pool))
        node_ids = {n.id for n in nodes}

        # existing live allocs keyed by (node, task group)
        # (reference system_util.go:299 diffSystemAllocs)
        live: Dict[Tuple[str, str], Allocation] = {}
        for a in all_allocs:
            if a.terminal_status():
                continue
            live[(a.node_id, a.task_group)] = a

        # stop allocs on nodes that are gone/ineligible or whose group vanished
        if job is not None:
            valid_groups = {tg.name for tg in job.task_groups}
        else:
            valid_groups = set()
        for (node_id, tg_name), a in live.items():
            if node_id in tainted:
                continue  # handled via lost/migrate path
            if stopped or tg_name not in valid_groups:
                self.plan.append_stopped_alloc(a, "alloc not needed")
                continue
            if node_id in node_ids:
                continue
            node = self.state.node_by_id(node_id)
            if node is not None and node.in_pool(job.datacenters, job.node_pool):
                # node exists in the job's DC/pool but is not ready (e.g.
                # marked scheduling-ineligible pre-maintenance):
                # ineligibility only blocks new placements, running allocs
                # stay (reference system_util.go:200 ignores allocs on
                # notReadyNodes instead of stopping them)
                continue
            self.plan.append_stopped_alloc(a, "alloc not needed")

        if not stopped:
            ctx.eligibility.set_job(job)
            preemption_enabled = (
                self.sched_config.preemption_enabled_for(job.type)
                if self.sched_config is not None else True)
            now = time.time()
            # sysbatch: completed allocs shouldn't rerun
            completed = ({(a.node_id, a.task_group) for a in all_allocs
                          if a.client_status == enums.ALLOC_CLIENT_COMPLETE
                          and a.job_version == job.version}
                         if self.sysbatch else set())
            for tg in job.task_groups:
                scorer = NodeScorer(ctx, job, tg,
                                    preemption_enabled=preemption_enabled,
                                    current_priority=job.priority)
                device = self._device_placer(ctx, tg)
                fresh: List = []
                for node in nodes:
                    existing = live.get((node.id, tg.name))
                    if existing is not None:
                        if existing.job_version == job.version:
                            continue  # in place and current
                        # destructive update
                        self.plan.append_stopped_alloc(
                            existing, "alloc is being updated due to job update")
                    if (node.id, tg.name) in completed:
                        continue
                    if existing is None and device is not None:
                        fresh.append(node)
                        continue
                    self._place_host(ctx, scorer, job, tg, node, now,
                                     existing)
                # at or under the tier's cut-over a launch's fixed cost
                # outweighs the host scorer's nodes
                if fresh and len(fresh) > device.HOST_CUTOVER:
                    self._place_device(ctx, device, job, tg, nodes, fresh,
                                       preemption_enabled, now)
                else:
                    for node in fresh:
                        self._place_host(ctx, scorer, job, tg, node, now,
                                         None)

        if self.plan.is_no_op() and not self.failed_tg_allocs:
            self._set_status(enums.EVAL_STATUS_COMPLETE, "")
            return True

        result, new_state = self.planner.submit_plan(self.plan)
        if new_state is not None:
            self.state = new_state
            full, _, _ = result.full_commit(self.plan)
            if not full:
                return False
        self._set_status(enums.EVAL_STATUS_COMPLETE, "")
        return True

    def _record_failure(self, tg_name: str, metrics, n: int = 1) -> None:
        # system jobs don't create blocked evals; they surface failed
        # placements on the eval (reference scheduler_system.go)
        if n <= 0:
            return
        prev = self.failed_tg_allocs.get(tg_name)
        if prev is None:
            self.failed_tg_allocs[tg_name] = metrics
            metrics.coalesced_failures += n - 1
        else:
            prev.coalesced_failures += n

    def _place_host(self, ctx, scorer, job, tg, node, now,
                    existing) -> None:
        """One node through the exact host scorer (NodeScorer.rank)."""
        metrics = ctx.new_metrics()
        metrics.nodes_evaluated += 1
        if not _class_feasible(ctx, job, tg, node):
            self._record_failure(tg.name, metrics)
            return
        option = scorer.rank(node)
        if option is None:
            self._record_failure(tg.name, metrics)
            return
        self._place_row(ctx, job, tg, node, option, metrics, now, existing)

    def _place_row(self, ctx, job, tg, node, option, metrics, now,
                   existing) -> None:
        """One placement as an Allocation row, its victims as rows."""
        alloc = Allocation(
            id=generate_uuid(),
            eval_id=self.eval.id,
            name=alloc_name(job.id, tg.name, 0),
            namespace=job.namespace,
            node_id=node.id,
            node_name=node.name,
            job_id=job.id,
            job=job,
            job_version=job.version,
            task_group=tg.name,
            allocated_vec=tg.combined_resources().vec(),
            allocated_ports=list(option.allocated_ports),
            allocated_devices=dict(option.allocated_devices),
            allocated_cores=list(option.allocated_cores),
            desired_status=enums.ALLOC_DESIRED_RUN,
            client_status=enums.ALLOC_CLIENT_PENDING,
            metrics=metrics,
            allocated_at=now,
        )
        if existing is not None:
            alloc.previous_allocation = existing.id
        if option.preempted_allocs:
            for victim in option.preempted_allocs:
                self.plan.append_preempted_alloc(victim, alloc.id)
        self.plan.append_alloc(alloc)
        self.queued_allocs[tg.name] = self.queued_allocs.get(tg.name, 0) + 1

    def _device_placer(self, ctx, tg):
        """The placer whose place_system takes this group, or None: the
        tpu-* algorithms, for a group that asks no device, core, volume
        or port by number (those keep the host scorer, node by node)."""
        placer = self.placer
        if placer is None:
            algorithm = (self.sched_config.scheduler_algorithm
                         if self.sched_config is not None else "")
            if algorithm not in (enums.SCHED_ALG_TPU_BINPACK,
                                 enums.SCHED_ALG_TPU_SOLVE):
                return None
            from .placer import placer_for_algorithm

            placer = self.placer = placer_for_algorithm(algorithm)
        if getattr(placer, "place_system", None) is None:
            return None
        ask = ctx.tg_resources(tg)
        if (ask.devices or ask.cores or ask.reserved_port_asks()
                or tg.volumes):
            return None
        return placer

    def _place_device(self, ctx, placer, job, tg, nodes, fresh,
                      preemption_enabled: bool, now: float) -> None:
        """The fresh nodes of one group through the device path
        (TPUPlacer.place_system) and its hand-over: the placements as
        ONE AllocBlock that carries their ports, their evictions as
        columns (plan.preempted), and what the host scorer decided
        instead as rows. Span system.block (args `nodes`, `victims`)
        around the hand-over."""
        import numpy as np

        from ..obs import TRACER
        from ..structs.alloc import AllocMetric
        from ..tensor.placer import _count_preempt

        out = placer.place_system(ctx, job, tg, nodes, fresh,
                                  preemption_enabled=preemption_enabled)
        with TRACER.span("system.block") as span:
            cluster = out.cluster
            index = cluster.node_index
            want = np.zeros(len(out.placed), dtype=bool)
            want[[index[node.id] for node in fresh]] = True
            exact = np.zeros(len(out.placed), dtype=bool)
            exact[list(out.exact_rows)] = True
            infeasible = int((want & ~out.feasible).sum())
            exhausted = int((out.feasible & ~out.placed & ~exact).sum())
            if infeasible:
                m = AllocMetric(nodes_evaluated=1)
                m.filter_node("constraints")
                self._record_failure(tg.name, m, infeasible)
            if exhausted:
                m = AllocMetric(nodes_evaluated=1)
                m.exhaust_node("resources")
                self._record_failure(tg.name, m, exhausted)
            for r, option, metrics in out.fallback:
                if option is None:
                    self._record_failure(tg.name, metrics)
                else:
                    self._place_row(ctx, job, tg, cluster.nodes[r], option,
                                    metrics, now, None)
            rows = out.device_rows()
            span.set(nodes=len(rows))
            victims = self._hand_over(ctx, job, tg, nodes, out, rows, now)
            span.set(victims=victims)
        _count_preempt(
            preempting_nodes=out.n_preempting,
            victims=victims + sum(
                len(option.preempted_allocs or ())
                for _, option, _ in out.fallback if option is not None),
            host_fallback_nodes=len(out.fallback))

    def _hand_over(self, ctx, job, tg, nodes, out, rows, now) -> int:
        """The device's placements on `rows` as ONE AllocBlock with its
        port column, their victims as columns of plan.preempted
        -> the number of victims."""
        import numpy as np

        from ..structs.alloc import BLOCK_SEP, AllocBlock
        from ..structs.plan import PreemptedPositions

        if not len(rows):
            return 0
        nodes_of = out.cluster.nodes
        scores = out.scores(rows)
        block = AllocBlock(
            id=generate_uuid(),
            eval_id=self.eval.id,
            namespace=job.namespace,
            job_id=job.id,
            job=job,
            job_version=job.version,
            task_group=tg.name,
            name_indices=np.zeros(len(rows), dtype=np.int64),
            node_ids=[nodes_of[r].id for r in rows.tolist()],
            node_names=[nodes_of[r].name for r in rows.tolist()],
            counts=np.ones(len(rows), dtype=np.int64),
            allocated_vec=ctx.tg_vec(tg),
            mean_score=float(scores.mean()),
            scores=scores,
            nodes_evaluated=1,
            nodes_in_pool=len(nodes),
            ports=out.block_ports(rows),
            allocated_at=now,
        )
        self.plan.append_block(block)
        self.queued_allocs[tg.name] = (
            self.queued_allocs.get(tg.name, 0) + len(rows))
        at, cols = np.nonzero(out.victims[rows])
        if not len(at):
            return 0
        vt = out.vt
        taken = PreemptedPositions(
            alloc_ids=vt.ids_at(rows[at], cols),
            node_ids=[block.node_ids[q] for q in at.tolist()],
            by=[f"{block.id}{BLOCK_SEP}{q}" for q in at.tolist()],
            vecs=vt.vec[rows[at], cols].astype(np.float64),
            groups=vt.groups_at(rows[at], cols))
        have = self.plan.preempted
        self.plan.preempted = have.concat(taken) if have else taken
        return len(at)

    @property
    def annotations(self):
        """Per-TG desired-update counts for the dry-run plan endpoint
        (system jobs: one placement per eligible node)."""
        return {tg: {"place": n} for tg, n in self.queued_allocs.items()}

    def _set_status(self, status: str, desc: str) -> None:
        ev = _copy.copy(self.eval)
        ev.status = status
        ev.status_description = desc
        ev.failed_tg_allocs = self.failed_tg_allocs
        ev.queued_allocations = dict(self.queued_allocs)
        self.planner.update_eval(ev)
