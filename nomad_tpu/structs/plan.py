"""Plan / PlanResult (reference structs.go Plan:12582, PlanResult:12837).

A plan is a scheduler's *proposed* state mutation: placements, evictions
and preemptions keyed by node. It is submitted to the leader's serialized
plan applier which re-verifies per-node fit against the latest state and
may partially commit (reference nomad/plan_apply.go:96-211).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


@dataclass(slots=True)
class PreemptedPositions:
    """The allocations a plan preempts, as columns, one entry a victim:
    its id (a block position "<block>.<p>" or a row's), its node, the
    id of the placement it makes room for, its allocated_vec and its
    (namespace, job id, task group). The system scheduler's device
    path hands its evictions over this way, beside the AllocBlock of
    its placements: no Allocation is built for a victim until the store
    commits the eviction, and the applier checks the nodes as arrays
    (core/plan_apply.py)."""

    alloc_ids: List[str] = field(default_factory=list)
    node_ids: List[str] = field(default_factory=list)
    by: List[str] = field(default_factory=list)
    vecs: np.ndarray = field(default_factory=lambda: np.zeros((0, 0)))
    groups: List[tuple] = field(default_factory=list)
    # node id -> its victims' ids (built on first use, never serialized)
    _by_node: Optional[dict] = field(default=None, repr=False,
                                     compare=False)

    def __len__(self) -> int:
        return len(self.alloc_ids)

    def on_node(self, node_id: str) -> List[str]:
        by_node = self._by_node
        if by_node is None:
            by_node = self._by_node = {}
            for aid, nid in zip(self.alloc_ids, self.node_ids):
                by_node.setdefault(nid, []).append(aid)
        return by_node.get(node_id, [])

    def take(self, keep: List[int]) -> "PreemptedPositions":
        """The entries at the indices `keep`, in that order."""
        if len(keep) == len(self.alloc_ids):
            return self
        return PreemptedPositions(
            [self.alloc_ids[i] for i in keep],
            [self.node_ids[i] for i in keep], [self.by[i] for i in keep],
            self.vecs[keep], [self.groups[i] for i in keep])

    def without_nodes(self, node_ids) -> "PreemptedPositions":
        """The entries whose node is not in `node_ids` (a partial
        commit's rejected nodes)."""
        bad = set(node_ids)
        return self.take([i for i, nid in enumerate(self.node_ids)
                          if nid not in bad])

    def concat(self, more: "PreemptedPositions") -> "PreemptedPositions":
        """These entries, then `more`'s (a job's groups in turn)."""
        return PreemptedPositions(
            self.alloc_ids + more.alloc_ids, self.node_ids + more.node_ids,
            self.by + more.by, np.concatenate([self.vecs, more.vecs]),
            self.groups + more.groups)


@dataclass(slots=True)
class Plan:
    eval_id: str = ""
    priority: int = 50
    job: object = None
    all_at_once: bool = False
    # node id -> allocs to stop/evict (full alloc rows with updated desired status)
    node_update: Dict[str, list] = field(default_factory=dict)
    # node id -> new/updated allocs to place
    node_allocation: Dict[str, list] = field(default_factory=dict)
    # node id -> allocs preempted to make room
    node_preemptions: Dict[str, list] = field(default_factory=dict)
    deployment: object = None
    deployment_updates: List[object] = field(default_factory=list)
    eval_updates: List[object] = field(default_factory=list)   # e.g. blocked eval created atomically
    annotations: Optional[dict] = None
    snapshot_index: int = 0
    # columnar bulk placements (structs/alloc.py AllocBlock): the C2M
    # path ships one record batch per (eval, task group) instead of K
    # Allocation objects; the applier verifies/commits them per node row
    alloc_blocks: List[object] = field(default_factory=list)
    # callbacks invoked with the PlanResult right after the planner
    # applies this plan (never serialized; process-local). The bulk
    # solver service uses these to confirm or correct its
    # device-resident usage overlay (tensor/solver.py ledger).
    post_apply_hooks: List[object] = field(default_factory=list)
    # evictions as columns (PreemptedPositions), beside node_preemptions'
    # rows; None while the plan has none
    preempted: Optional[PreemptedPositions] = None

    def append_alloc(self, alloc) -> None:
        self.node_allocation.setdefault(alloc.node_id, []).append(alloc)

    def append_block(self, block) -> None:
        self.alloc_blocks.append(block)

    def block_allocs_for_node(self, node_id: str) -> list:
        """Materialized block placements on one node (the applier's exact
        per-node check path; rare — block nodes normally verify via the
        vectorized pass)."""
        out = []
        for b in self.alloc_blocks:
            out.extend(b.allocs_for_node(node_id))
        return out

    def append_stopped_alloc(self, alloc, desired_desc: str, client_status: str = "") -> None:
        """Mark an alloc for stopping (reference structs.go Plan.AppendStoppedAlloc)."""
        from . import enums

        updated = alloc.copy_for_update()
        updated.desired_status = enums.ALLOC_DESIRED_STOP
        updated.desired_description = desired_desc
        if client_status:
            updated.client_status = client_status
        self.node_update.setdefault(alloc.node_id, []).append(updated)

    def append_preempted_alloc(self, alloc, preempting_alloc_id: str) -> None:
        from . import enums

        updated = alloc.copy_for_update()
        updated.desired_status = enums.ALLOC_DESIRED_EVICT
        updated.desired_description = f"Preempted by alloc ID {preempting_alloc_id}"
        updated.preempted_by_allocation = preempting_alloc_id
        self.node_preemptions.setdefault(alloc.node_id, []).append(updated)

    def is_no_op(self) -> bool:
        return (
            not self.node_update
            and not self.node_allocation
            and not self.node_preemptions
            and not self.alloc_blocks
            and not self.preempted
            and self.deployment is None
            and not self.deployment_updates
        )

    def normalize(self) -> None:
        """Strip job copies from stop/preempt rows before 'raft' apply
        (reference plan normalization, structs.go Plan.NormalizeAllocations)."""
        for allocs in self.node_update.values():
            for a in allocs:
                a.job = None
        for allocs in self.node_preemptions.values():
            for a in allocs:
                a.job = None


@dataclass(slots=True)
class PlanResult:
    """What the plan applier actually committed (reference structs.go PlanResult:12837)."""

    node_update: Dict[str, list] = field(default_factory=dict)
    node_allocation: Dict[str, list] = field(default_factory=dict)
    node_preemptions: Dict[str, list] = field(default_factory=dict)
    # committed AllocBlocks (possibly sliced: rejected node rows marked)
    alloc_blocks: List[object] = field(default_factory=list)
    # the plan's column evictions on the nodes that were not rejected
    preempted: Optional[PreemptedPositions] = None
    # one evaluation per job that lost allocations to this plan,
    # triggered by "preemption" (committed with it)
    preemption_evals: List[object] = field(default_factory=list)
    deployment: object = None
    deployment_updates: List[object] = field(default_factory=list)
    # If set, the plan was partially committed and the scheduler should
    # refresh its snapshot to at least this index before retrying
    # (reference plan_apply.go partial commit + RefreshIndex).
    refresh_index: int = 0
    alloc_index: int = 0
    rejected_nodes: List[str] = field(default_factory=list)

    def full_commit(self, plan: Plan) -> tuple:
        """(fully_committed, num_expected, num_actual)
        (reference structs.go PlanResult.FullCommit)."""
        expected = sum(len(v) for v in plan.node_allocation.values())
        expected += sum(b.size for b in plan.alloc_blocks)
        actual = sum(len(v) for v in self.node_allocation.values())
        actual += sum(b.live_size() for b in self.alloc_blocks)
        return expected == actual, expected, actual

    def is_no_op(self) -> bool:
        return (
            not self.node_update
            and not self.node_allocation
            and not self.alloc_blocks
            and not self.preempted
            and not self.deployment_updates
            and self.deployment is None
        )
