"""Preemption victim selection (reference scheduler/preemption.go, 779 LoC).

Implements the reference's heuristics:

- only allocations at least PRIORITY_DELTA (10) below the asking job's
  priority are evictable (preemption.go filterAndGroupPreemptibleAllocs);
- candidates are considered in ascending priority groups and chosen by
  resource distance — how closely the victim's resources match the
  remaining need (preemption.go basicResourceDistance) — stopping as
  soon as the ask fits, then redundant victims are dropped
  (filterSuperset);
- a victim whose task group is already at its migrate max_parallel in
  this selection takes a score penalty of MAX_PARALLEL_PENALTY (50) per
  excess eviction (preemption.go:16 maxParallelPenalty,
  scoreForTaskGroup);
- network preemption frees conflicting reserved ports / mbits by
  network resource distance (preemption.go PreemptForNetwork,
  networkResourceDistance);
- device preemption frees device-group instances, preferring the victim
  set with minimal net priority, largest holders first
  (preemption.go PreemptForDevice, selectBestAllocs).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..structs import allocs_fit
from ..structs.alloc import Allocation
from ..structs.resources import RESOURCE_DIMS

# reference preemption.go:26 — "skip allocs whose priority is within a
# delta of 10"
PRIORITY_DELTA = 10
# reference preemption.go:16 maxParallelPenalty
MAX_PARALLEL_PENALTY = 50.0


def is_preemptible(alloc: Allocation, current_priority: int) -> bool:
    return (alloc.job is not None
            and current_priority - alloc.job.priority >= PRIORITY_DELTA
            and alloc.should_count_for_usage())


def victim_candidates(proposed: Sequence[Allocation],
                      current_priority: int) -> List[Allocation]:
    """Eligible victims in the CANONICAL COLUMN ORDER the in-kernel
    prefix rule consumes: priority ascending (the reference's
    filterAndGroupPreemptibleAllocs group order), alloc id ascending
    within a priority tie so the order is deterministic across
    processes (leader failover replaying an eval must select the same
    victims). This is the single eligibility definition shared by the
    exact scanner below, the tensor victim-column builder
    (tensor/cluster.build_victim_tensors), and the preempt_solve kernel
    parity oracle."""
    cands = [a for a in proposed if is_preemptible(a, current_priority)]
    cands.sort(key=lambda a: (a.job.priority, a.id))
    return cands


def victim_holds_exact_resources(alloc: Allocation) -> bool:
    """True when evicting this alloc changes state the dense resource
    columns can't model — reserved/dynamic port numbers or concrete
    device instances. The preempt_solve kernel flags any row whose
    victim set includes such an alloc so the placer re-routes that one
    request through the exact host scanner (preempt_for_network /
    preempt_for_device semantics)."""
    return bool(alloc.allocated_ports) or bool(alloc.allocated_devices)


def basic_resource_distance(need: np.ndarray, have: np.ndarray) -> float:
    """Euclidean distance between normalized resource vectors
    (reference preemption.go basicResourceDistance)."""
    d = 0.0
    for i in range(RESOURCE_DIMS):
        if need[i] > 0:
            d += ((have[i] - need[i]) / need[i]) ** 2
    return float(np.sqrt(d))


def plan_preempted_counts(plan) -> Dict[tuple, int]:
    """Evictions already in `plan` per (namespace, job id, task group):
    its rows (node_preemptions) and its columns (plan.preempted) alike
    (reference preemption.go SetPreemptions over the plan's
    NodePreemptions)."""
    counts: Dict[tuple, int] = {}
    if plan is None:
        return counts
    for allocs in plan.node_preemptions.values():
        for a in allocs:
            k = (a.namespace, a.job_id, a.task_group)
            counts[k] = counts.get(k, 0) + 1
    if plan.preempted:
        for k in plan.preempted.groups:
            k = tuple(k)
            counts[k] = counts.get(k, 0) + 1
    return counts


def max_parallel_of(job, tg_name: str) -> int:
    """The migrate max_parallel a victim's group is held to, 0 for
    none (what _max_parallel_penalty reads)."""
    if job is None:
        return 0
    tg = job.lookup_task_group(tg_name)
    if tg is None or tg.migrate is None:
        return 0
    return max(0, int(tg.migrate.max_parallel))


def _max_parallel_penalty(alloc: Allocation, counts: Dict[tuple, int]) -> float:
    """Score penalty once a victim's task group is at its migrate
    max_parallel in this selection (reference scoreForTaskGroup)."""
    max_parallel = max_parallel_of(alloc.job, alloc.task_group)
    if max_parallel <= 0:
        return 0.0
    n = counts.get((alloc.namespace, alloc.job_id, alloc.task_group), 0)
    if n < max_parallel:
        return 0.0
    return float((n + 1) - max_parallel) * MAX_PARALLEL_PENALTY


def preempt_for_task_group(
    node,
    proposed: Sequence[Allocation],
    ask_vec: np.ndarray,
    current_priority: int,
    preempted_counts: Optional[Dict[tuple, int]] = None,
) -> Optional[List[Allocation]]:
    """Pick a minimal set of lower-priority allocs whose removal lets the
    ask's resources fit (reference preemption.go:127 PreemptForTaskGroup;
    device instances are preempt_for_device's, after this). Returns
    None/empty when impossible. `preempted_counts` carries per-(ns, job,
    tg) evictions already in the plan so migrate max_parallel penalties
    apply across the whole eval."""
    # shared eligibility + canonical priority-ascending order; within a
    # group the loop below prefers the alloc whose resources best match
    # what's still missing (smallest distance to need, plus the
    # max_parallel penalty)
    candidates = victim_candidates(proposed, current_priority)
    if not candidates:
        return None

    counts: Dict[tuple, int] = dict(preempted_counts or {})

    victims: List[Allocation] = []
    victim_ids = set()

    placement = Allocation(id="_cand", allocated_vec=ask_vec)

    def fits_now() -> bool:
        remaining = [a for a in proposed if a.id not in victim_ids]
        fit, _, _ = allocs_fit(node, remaining + [placement])
        return fit

    if fits_now():
        return None  # nothing to preempt; caller shouldn't have asked

    # iterate priority groups from lowest
    i = 0
    while i < len(candidates):
        prio = candidates[i].job.priority
        group = []
        while i < len(candidates) and candidates[i].job.priority == prio:
            group.append(candidates[i])
            i += 1
        # within the group, repeatedly take the best-matching alloc
        while group:
            # distance to the *remaining* need
            used = np.zeros(RESOURCE_DIMS)
            for a in proposed:
                if a.id not in victim_ids and a.should_count_for_usage():
                    used += a.allocated_vec
            need = used + ask_vec - node.available_vec()
            need = np.maximum(need, 0.0)
            # the closest, ties to the first in canonical order (the
            # group keeps that order: the device path's columns are it)
            best = min(range(len(group)), key=lambda k: (
                basic_resource_distance(need, group[k].allocated_vec)
                + _max_parallel_penalty(group[k], counts), k))
            pick = group.pop(best)
            victims.append(pick)
            victim_ids.add(pick.id)
            ckey = (pick.namespace, pick.job_id, pick.task_group)
            counts[ckey] = counts.get(ckey, 0) + 1
            if fits_now():
                # drop any victim that is no longer necessary (reference
                # filterSuperset behavior: remove redundant evictions)
                for v in sorted(victims, key=lambda a: -a.job.priority):
                    victim_ids.discard(v.id)
                    if not fits_now():
                        victim_ids.add(v.id)
                return [v for v in victims if v.id in victim_ids]
    return None


def preempt_for_network(
    node,
    proposed: Sequence[Allocation],
    ask,
    current_priority: int,
    preempted_counts: Optional[Dict[tuple, int]] = None,
) -> Optional[List[Allocation]]:
    """Free conflicting reserved ports (reference preemption.go:30
    PreemptForNetwork). The reference also preempts on bandwidth
    (networkResourceDistance over mbits); this model's allocations
    record ports but not per-alloc bandwidth, so the network dimension
    here is reserved-port conflicts — victims are taken in ascending
    priority groups, direct holders of a needed port first, with the
    migrate max_parallel penalty applied (scoreForNetwork)."""
    needed_ports = {p[1] for p in ask.reserved_port_asks()}
    if not needed_ports:
        return None

    counts: Dict[tuple, int] = dict(preempted_counts or {})

    def alloc_ports(a: Allocation) -> set:
        return {p.value for p in a.allocated_ports}

    candidates = [a for a in proposed if is_preemptible(a, current_priority)
                  and alloc_ports(a) & needed_ports]
    if not candidates:
        return None

    victims: List[Allocation] = []
    victim_ids = set()

    def satisfied() -> bool:
        for a in proposed:
            if a.id in victim_ids or not a.should_count_for_usage():
                continue
            if alloc_ports(a) & needed_ports:
                return False
        return True

    if satisfied():
        return None

    candidates.sort(key=lambda a: a.job.priority)
    i = 0
    while i < len(candidates):
        prio = candidates[i].job.priority
        group = []
        while i < len(candidates) and candidates[i].job.priority == prio:
            group.append(candidates[i])
            i += 1
        while group:
            group.sort(key=lambda a: (
                -len(alloc_ports(a) & needed_ports)
                + _max_parallel_penalty(a, counts)))
            pick = group.pop(0)
            victims.append(pick)
            victim_ids.add(pick.id)
            ckey = (pick.namespace, pick.job_id, pick.task_group)
            counts[ckey] = counts.get(ckey, 0) + 1
            if satisfied():
                return victims
    return None


def preempt_for_device(
    node,
    proposed: Sequence[Allocation],
    ask_devices,
    current_priority: int,
) -> Optional[List[Allocation]]:
    """Free device-group instances (reference preemption.go:16
    PreemptForDevice + selectBestAllocs): per unsatisfied ask, victims
    come from ascending priority groups, largest instance holders first,
    until enough instances are free."""
    from .devices import matching_groups

    victims: List[Allocation] = []
    victim_ids = set()

    for ask in ask_devices:
        groups = matching_groups(node, ask, {}, {})
        group_ids = {g.id for g in groups}
        capacity = sum(len(g.instance_ids) for g in groups)

        def held_instances(a: Allocation) -> int:
            return sum(len(inst)
                       for name, inst in (a.allocated_devices or {}).items()
                       if name in group_ids)

        def free_now() -> int:
            used = 0
            for a in proposed:
                if a.id in victim_ids or not a.should_count_for_usage():
                    continue
                used += held_instances(a)
            return capacity - used

        needed = ask.count - free_now()
        if needed <= 0:
            continue
        candidates = [a for a in proposed
                      if is_preemptible(a, current_priority)
                      and held_instances(a) > 0]
        if not candidates:
            return None
        # ascending priority, then largest holders first within a group
        # (reference selectBestAllocs sorts descending by instance count)
        candidates.sort(key=lambda a: (a.job.priority, -held_instances(a)))
        freed = 0
        for a in candidates:
            if freed >= needed:
                break
            victims.append(a)
            victim_ids.add(a.id)
            freed += held_instances(a)
        if freed < needed:
            return None
    return victims or None
