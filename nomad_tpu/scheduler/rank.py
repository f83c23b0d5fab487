"""Ranking & selection — the greedy host path
(reference scheduler/rank.go + select.go + stack.go).

Reproduces the reference iterator chain as a straight-line pass:

  shuffled nodes -> class-memoized feasibility -> distinct hosts/property
  -> binpack fit (AllocsFit + ScoreFitBinPack/Spread, preemption fallback)
  -> job anti-affinity -> rescheduling penalty -> node affinity -> spread
  -> mean normalization -> limit(log2 n, skip<=3 below 0.0) -> max score

This is the oracle the TPU kernels are differential-tested against, and
the production path for the classic "binpack"/"spread" algorithms.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Sequence

import numpy as np

from ..structs import (
    BINPACK_MAX_FIT_SCORE,
    Job,
    Node,
    TaskGroup,
    allocs_fit,
    enums,
    score_fit_binpack,
    score_fit_spread,
)
from ..structs.alloc import Allocation
from .context import EvalContext
from .feasible import (
    distinct_hosts_mask,
    distinct_property_mask,
    feasible_mask,
    job_constraints,
    node_meets_constraint,
    resolve_target,
)
from .spread import SpreadScorer

# reference scheduler/stack.go:13-21
SKIP_SCORE_THRESHOLD = 0.0
MAX_SKIP = 3


@dataclass
class RankedNode:
    """Reference scheduler/rank.go:24 RankedNode."""

    node: Node
    scores: List[float] = field(default_factory=list)
    score_meta: Dict[str, float] = field(default_factory=dict)
    final_score: float = 0.0
    preempted_allocs: Optional[List[Allocation]] = None
    allocated_ports: List = field(default_factory=list)
    allocated_devices: Dict[str, List[str]] = field(default_factory=dict)
    allocated_cores: List[int] = field(default_factory=list)

    def add_score(self, name: str, value: float) -> None:
        self.scores.append(value)
        self.score_meta[name] = value

    def normalize(self) -> None:
        """Mean of sub-scores (reference rank.go:800 ScoreNormalizationIterator)."""
        if self.scores:
            self.final_score = sum(self.scores) / len(self.scores)
        self.score_meta["normalized-score"] = self.final_score


def net_priority(allocs: Sequence[Allocation]) -> float:
    """Reference rank.go:864 netPriority."""
    total, mx = 0, 0.0
    for a in allocs:
        p = a.job.priority if a.job is not None else 50
        mx = max(mx, float(p))
        total += p
    return mx + (total / mx) if mx else 0.0


def preemption_score(net_prio: float) -> float:
    """Logistic with inflection at 2048 (reference rank.go:894)."""
    rate, origin = 0.0048, 2048.0
    return 1.0 / (1.0 + math.exp(rate * (net_prio - origin)))


class NodeScorer:
    """Scores one candidate node for one task-group placement.

    Holds per-(job, tg) state shared across the placements of a single
    evaluation: merged affinities, spread property sets, penalty nodes.
    """

    def __init__(self, ctx: EvalContext, job: Job, tg: TaskGroup, *,
                 algorithm: str = enums.SCHED_ALG_BINPACK,
                 preemption_enabled: bool = False,
                 current_priority: int = 0):
        self.ctx = ctx
        self.job = job
        self.tg = tg
        self.algorithm = algorithm
        self.preemption_enabled = preemption_enabled
        self.current_priority = current_priority or job.priority
        self.ask = tg.combined_resources()
        self.ask_vec = self.ask.vec()
        self.wants_ports = bool(
            self.ask.reserved_port_asks() or self.ask.dynamic_port_count())
        self.affinities = (
            list(job.affinities) + list(tg.affinities)
            + [a for t in tg.tasks for a in t.affinities]
        )
        self.sum_affinity_weight = sum(abs(a.weight) for a in self.affinities)
        self.spread = SpreadScorer(job, tg, ctx.snapshot)
        self.penalty_nodes: FrozenSet[str] = frozenset()
        self._ppc_cache = None

    def has_affinities_or_spreads(self) -> bool:
        return bool(self.affinities) or self.spread.has_spreads()

    def _plan_preempted_counts(self) -> dict:
        """Evictions already in the in-progress plan per (ns, job, tg),
        so migrate max_parallel penalties span the whole eval
        (reference preemption.go scoreForTaskGroup numPreemptedAllocs).
        Cached against the plan's total preemption count — a full-cluster
        scan calls rank() per node and must not rebuild an identical dict
        every time."""
        plan = self.ctx.plan
        if plan is None:
            return {}
        total = sum(len(v) for v in plan.node_preemptions.values())
        cached = self._ppc_cache
        if cached is not None and cached[0] == total:
            return cached[1]
        counts: dict = {}
        for allocs in plan.node_preemptions.values():
            for a in allocs:
                k = (a.namespace, a.job_id, a.task_group)
                counts[k] = counts.get(k, 0) + 1
        self._ppc_cache = (total, counts)
        return counts

    # --- binpack fit (reference rank.go:205-587 BinPackIterator.Next) ---

    def rank(self, node: Node) -> Optional[RankedNode]:
        """Returns a scored RankedNode, or None if the node is exhausted
        (doesn't fit and preemption can't free room)."""
        option = RankedNode(node=node)
        proposed = self.ctx.proposed_allocs(node.id)

        placement = Allocation(
            id="_candidate", allocated_vec=self.ask_vec,
            job_id=self.job.id, task_group=self.tg.name,
            client_status=enums.ALLOC_CLIENT_PENDING,
        )
        check_devices = bool(self.ask.devices)
        fit, dim, used = allocs_fit(node, proposed + [placement], check_devices=check_devices)
        if not fit:
            if dim.startswith("port collision"):
                # committed state already double-books a port: sanitizer
                # signal (reference context.go:84 PortCollisionEvent from
                # rank.go:226-249)
                from ..structs.network import check_port_collisions

                self.ctx.send_event({
                    "type": "port_collision", "node_id": node.id,
                    "ports": check_port_collisions(node, proposed)})
            if not self.preemption_enabled:
                if self.ctx.metrics is not None:
                    self.ctx.metrics.exhaust_node(dim)
                return None
            from .preemption import preempt_for_task_group

            victims = preempt_for_task_group(
                node, proposed, self.ask_vec, self.current_priority,
                check_devices=check_devices, ask_devices=self.ask.devices,
                preempted_counts=self._plan_preempted_counts())
            if not victims:
                if self.ctx.metrics is not None:
                    self.ctx.metrics.exhaust_node(dim)
                return None
            option.preempted_allocs = victims
            remaining = [a for a in proposed if a.id not in {v.id for v in victims}]
            fit, dim, used = allocs_fit(node, remaining + [placement],
                                        check_devices=check_devices)
            if not fit:
                if self.ctx.metrics is not None:
                    self.ctx.metrics.exhaust_node(dim)
                return None

        # --- port assignment (reference rank.go:226-249: NetworkIndex
        # SetAllocs + AssignPorts inside BinPackIterator.Next) ---
        if self.wants_ports:
            counted = proposed if option.preempted_allocs is None else [
                a for a in proposed
                if a.id not in {v.id for v in option.preempted_allocs}]
            idx = self.ctx.port_index(node, counted)
            ports, err = idx.assign_ports(self.ask)
            if err and self.preemption_enabled:
                # reserved-port conflict: free the holders (reference
                # rank.go preemption fallback -> PreemptForNetwork)
                from .preemption import preempt_for_network

                net_victims = preempt_for_network(
                    node, counted, self.ask, self.current_priority,
                    preempted_counts=self._plan_preempted_counts())
                if net_victims:
                    option.preempted_allocs = (
                        (option.preempted_allocs or []) + net_victims)
                    victim_ids = {v.id for v in option.preempted_allocs}
                    counted = [a for a in counted if a.id not in victim_ids]
                    idx = self.ctx.port_index(node, counted)
                    ports, err = idx.assign_ports(self.ask)
            if err:
                if self.ctx.metrics is not None:
                    self.ctx.metrics.exhaust_node("ports")
                return None
            option.allocated_ports = ports

        # --- device instance assignment + core selection (reference
        # rank.go:510-525: deviceAllocator offers + coreSelector) ---
        if self.ask.devices or self.ask.cores:
            if option.preempted_allocs is None:
                counted_for_ids = proposed
            else:
                victim_ids = {v.id for v in option.preempted_allocs}
                counted_for_ids = [a for a in proposed if a.id not in victim_ids]
        if self.ask.devices:
            from .devices import DeviceIndex, device_affinity_boost

            didx = DeviceIndex(node, counted_for_ids)
            assignment = didx.assign(self.ask.devices,
                                     self.ctx.regex_cache, self.ctx.version_cache)
            if assignment is None and self.preemption_enabled:
                # device instances exhausted: free holders (reference
                # rank.go fallback -> PreemptForDevice)
                from .preemption import preempt_for_device

                dev_victims = preempt_for_device(
                    node, counted_for_ids, self.ask.devices,
                    self.current_priority)
                if dev_victims:
                    option.preempted_allocs = (
                        (option.preempted_allocs or []) + dev_victims)
                    victim_ids = {v.id for v in option.preempted_allocs}
                    counted_for_ids = [a for a in counted_for_ids
                                       if a.id not in victim_ids]
                    didx = DeviceIndex(node, counted_for_ids)
                    assignment = didx.assign(self.ask.devices,
                                             self.ctx.regex_cache,
                                             self.ctx.version_cache)
            if assignment is None:
                if self.ctx.metrics is not None:
                    self.ctx.metrics.exhaust_node("devices")
                return None
            option.allocated_devices = assignment
            dev_boost = device_affinity_boost(
                node, self.ask.devices, self.ctx.regex_cache, self.ctx.version_cache)
            if dev_boost != 0.0:
                option.add_score("device-affinity", dev_boost)
        if self.ask.cores:
            from .devices import combined_numa_affinity, select_cores

            cores = select_cores(node, counted_for_ids, int(self.ask.cores),
                                 combined_numa_affinity(self.tg))
            if cores is None:
                if self.ctx.metrics is not None:
                    self.ctx.metrics.exhaust_node("cores")
                return None
            option.allocated_cores = cores

        if option.preempted_allocs is not None:
            # network/device preemption may have added victims after the
            # first fit pass: recompute usage so the binpack score sees
            # the node as the evictions leave it
            victim_ids = {v.id for v in option.preempted_allocs}
            remaining = [a for a in proposed if a.id not in victim_ids]
            _, _, used = allocs_fit(node, remaining + [placement],
                                    check_devices=check_devices)

        available = node.available_vec()
        if self.algorithm == enums.SCHED_ALG_SPREAD:
            fitness = score_fit_spread(available, used)
        else:
            fitness = score_fit_binpack(available, used)
        option.add_score("binpack", fitness / BINPACK_MAX_FIT_SCORE)

        # --- job anti-affinity (reference rank.go:596) ---
        collisions = sum(
            1 for a in proposed
            if a.job_id == self.job.id and a.task_group == self.tg.name
        )
        if collisions > 0 and self.tg.count > 0:
            option.add_score("job-anti-affinity", -float(collisions + 1) / self.tg.count)

        # --- rescheduling penalty (reference rank.go:666) ---
        if node.id in self.penalty_nodes:
            option.add_score("node-reschedule-penalty", -1.0)

        # --- node affinity (reference rank.go:710) ---
        if self.affinities:
            total = 0.0
            for aff in self.affinities:
                lval, lok = resolve_target(aff.ltarget, node)
                rval, rok = resolve_target(aff.rtarget, node)
                from .feasible import check_constraint

                if check_constraint(aff.operand, lval, rval, lok, rok,
                                    self.ctx.regex_cache, self.ctx.version_cache):
                    total += aff.weight
            if total != 0.0:
                option.add_score("node-affinity", total / self.sum_affinity_weight)

        # --- spread (reference spread.go:128) ---
        sboost = self.spread.score(node)
        if sboost is not None:
            option.add_score("allocation-spread", sboost)

        # --- preemption score (reference rank.go:835) ---
        if option.preempted_allocs:
            option.add_score("preemption", preemption_score(net_priority(option.preempted_allocs)))

        option.normalize()
        return option

    def record_placement(self, node: Node) -> None:
        self.spread.record_placement(node)


def _class_feasible(ctx: EvalContext, job: Job, tg: TaskGroup, node: Node) -> bool:
    """Class-memoized job+tg feasibility for one node (reference
    feasible.go:1115 FeasibilityWrapper + context.go EvalEligibility)."""
    from .feasible import device_mask, driver_mask, network_mask

    klass = node.computed_class
    elig = ctx.eligibility

    ok = elig.job_status(klass)
    if ok is None:
        ok = all(
            node_meets_constraint(c, node, ctx.regex_cache, ctx.version_cache)
            for c in job.constraints
        )
        elig.set_job_status(klass, ok)
    if not ok:
        if ctx.metrics is not None:
            ctx.metrics.filter_node("job constraints")
        return False

    ok = elig.tg_status(tg.name, klass)
    if ok is None:
        from .feasible import host_volume_mask

        tg_cons = list(tg.constraints) + [c for t in tg.tasks for c in t.constraints]
        ok = (
            bool(driver_mask(tg, [node])[0])
            and bool(device_mask(tg, [node])[0])
            and bool(network_mask(tg, [node])[0])
            and bool(host_volume_mask(tg, [node])[0])
            and all(
                node_meets_constraint(c, node, ctx.regex_cache, ctx.version_cache)
                for c in tg_cons
            )
        )
        elig.set_tg_status(tg.name, klass, ok)
    if not ok:
        if ctx.metrics is not None:
            ctx.metrics.filter_node("task group constraints")
        return False
    # csi-volume claims change independently of node classes: checked per
    # node, never memoized (reference feasible.go:223 CSIVolumeChecker)
    if any(v.type == "csi" for v in tg.volumes.values()):
        from .feasible import csi_volume_mask

        if not bool(csi_volume_mask(tg, [node], ctx.snapshot,
                                    job.namespace, ctx.plan)[0]):
            if ctx.metrics is not None:
                ctx.metrics.filter_node("csi volumes")
            return False
    return True


def _plan_aware_job_allocs(ctx: EvalContext, job: Job) -> List[Allocation]:
    """The job's allocs as they would look if the in-progress plan
    committed — state minus planned stops/evictions plus placements. Used
    by distinct_property so placements within one eval see each other."""
    out = list(ctx.snapshot.allocs_by_job(job.id, job.namespace))
    if ctx.plan is None:
        return out
    removed = set()
    for allocs in ctx.plan.node_update.values():
        removed.update(a.id for a in allocs)
    for allocs in ctx.plan.node_preemptions.values():
        removed.update(a.id for a in allocs)
    out = [a for a in out if a.id not in removed]
    for allocs in ctx.plan.node_allocation.values():
        out.extend(a for a in allocs if a.job_id == job.id)
    for block in ctx.plan.alloc_blocks:
        if block.job_id == job.id:
            out.extend(block.iter_allocs())
    return out


def select_best_node(
    ctx: EvalContext,
    job: Job,
    tg: TaskGroup,
    nodes: Sequence[Node],
    *,
    batch: bool = False,
    algorithm: str = enums.SCHED_ALG_BINPACK,
    preemption_enabled: bool = False,
    penalty_nodes: FrozenSet[str] = frozenset(),
    scorer: Optional[NodeScorer] = None,
    attempt: int = 0,
) -> Optional[RankedNode]:
    """One placement: the full GenericStack.Select
    (reference stack.go:128; limit math stack.go:82-95,176-185)."""
    t0 = time.perf_counter()
    metrics = ctx.new_metrics()
    metrics.nodes_in_pool = len(nodes)
    if not nodes:
        return None

    if scorer is None:
        scorer = NodeScorer(ctx, job, tg, algorithm=algorithm,
                            preemption_enabled=preemption_enabled)
    scorer.penalty_nodes = penalty_nodes

    # limit = 2 for batch (power of two choices), else ceil(log2 n) floored
    # at 2; spread/affinity jobs widen to max(tg.count, 100)
    n = len(nodes)
    if batch:
        limit = 2
    else:
        limit = max(2, int(math.ceil(math.log2(n))) if n > 1 else 2)
    if scorer.has_affinities_or_spreads():
        limit = max(tg.count, 100)

    shuffled = ctx.shuffled_nodes(list(nodes), attempt)

    best: Optional[RankedNode] = None
    seen = 0
    skipped: List[RankedNode] = []

    dh_needed = True  # distinct-hosts/property checks are cheap per-node
    for node in shuffled:
        if seen >= limit:
            break
        metrics.nodes_evaluated += 1
        if not _class_feasible(ctx, job, tg, node):
            continue
        if dh_needed:
            if not distinct_hosts_mask(job, tg, [node], ctx.proposed_allocs)[0]:
                metrics.filter_node("distinct_hosts")
                continue
            dprop = distinct_property_mask(
                job, tg, [node],
                _plan_aware_job_allocs(ctx, job),
                ctx.snapshot.node_by_id)
            if not dprop[0]:
                metrics.filter_node("distinct_property")
                continue
        option = scorer.rank(node)
        if option is None:
            continue
        # LimitIterator skip logic (reference select.go:8): up to MAX_SKIP
        # low-scoring options are set aside in hope of better ones
        if option.final_score <= SKIP_SCORE_THRESHOLD and len(skipped) < MAX_SKIP:
            skipped.append(option)
            continue
        seen += 1
        if best is None or option.final_score > best.final_score:
            best = option

    # feed skipped options back in for max-score consideration up to the
    # limit (reference select.go:8 LimitIterator nextOption fallback)
    for option in skipped:
        if seen >= limit:
            break
        seen += 1
        if best is None or option.final_score > best.final_score:
            best = option

    metrics.allocation_time_s = time.perf_counter() - t0
    if best is not None:
        for name, val in best.score_meta.items():
            metrics.scores[f"{best.node.id}.{name}"] = val
    return best


def score_nodes(ctx: EvalContext, job: Job, tg: TaskGroup, nodes: Sequence[Node],
                algorithm: str = enums.SCHED_ALG_BINPACK,
                preemption_enabled: bool = False) -> List[RankedNode]:
    """Score every feasible node (no limit/shuffle) — used by tests and
    the system scheduler, and as the oracle for kernel differential tests."""
    ctx.new_metrics()
    scorer = NodeScorer(ctx, job, tg, algorithm=algorithm,
                        preemption_enabled=preemption_enabled)
    out = []
    for node in nodes:
        if not _class_feasible(ctx, job, tg, node):
            continue
        option = scorer.rank(node)
        if option is not None:
            out.append(option)
    return out
