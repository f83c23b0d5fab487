"""Allocation + metrics (reference structs.go Allocation:10694, AllocMetric:11716)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import enums
from .resources import comparable


@dataclass(slots=True)
class RescheduleEvent:
    reschedule_time: float = 0.0
    prev_alloc_id: str = ""
    prev_node_id: str = ""
    delay_s: float = 0.0


@dataclass(slots=True)
class RescheduleTracker:
    """History of reschedule attempts, chained through replacements
    (reference structs.go RescheduleTracker; generic_sched.go:839)."""

    events: List[RescheduleEvent] = field(default_factory=list)


@dataclass(slots=True)
class DesiredTransition:
    """Server-requested transitions (reference structs.go DesiredTransition;
    set by the drainer and `alloc stop`)."""

    migrate: bool = False
    reschedule: bool = False
    force_reschedule: bool = False
    no_shutdown_delay: bool = False


@dataclass(slots=True)
class AllocMetric:
    """Why/how a placement was made (reference structs.go AllocMetric:11716;
    populated by the ranking pipeline and surfaced by `alloc status`)."""

    nodes_evaluated: int = 0
    nodes_filtered: int = 0
    nodes_in_pool: int = 0
    nodes_available: Dict[str, int] = field(default_factory=dict)       # per-dc
    class_filtered: Dict[str, int] = field(default_factory=dict)
    constraint_filtered: Dict[str, int] = field(default_factory=dict)
    nodes_exhausted: int = 0
    class_exhausted: Dict[str, int] = field(default_factory=dict)
    dimension_exhausted: Dict[str, int] = field(default_factory=dict)
    quota_exhausted: List[str] = field(default_factory=list)
    scores: Dict[str, float] = field(default_factory=dict)              # "node.scorer" -> score
    allocation_time_s: float = 0.0
    coalesced_failures: int = 0

    def exhaust_node(self, dimension: str) -> None:
        self.nodes_exhausted += 1
        if dimension:
            self.dimension_exhausted[dimension] = self.dimension_exhausted.get(dimension, 0) + 1

    def filter_node(self, reason: str) -> None:
        self.nodes_filtered += 1
        if reason:
            self.constraint_filtered[reason] = self.constraint_filtered.get(reason, 0) + 1


@dataclass(slots=True)
class TaskEvent:
    """One event in a task's lifecycle timeline
    (reference structs.go TaskEvent)."""

    type: str = ""           # Received|Task Setup|Started|Terminated|Restarting|Killed|Driver Failure|Not Restarting
    time: float = 0.0
    message: str = ""
    details: Dict[str, str] = field(default_factory=dict)
    exit_code: Optional[int] = None
    restart_reason: str = ""


@dataclass(slots=True)
class TaskState:
    """Client-observed state of one task (reference structs.go TaskState)."""

    state: str = "pending"   # pending | running | dead
    failed: bool = False
    restarts: int = 0
    last_restart: float = 0.0
    started_at: float = 0.0
    finished_at: float = 0.0
    events: List[TaskEvent] = field(default_factory=list)

    def successful(self) -> bool:
        return self.state == "dead" and not self.failed

    def copy(self) -> "TaskState":
        """Snapshot copy — runner threads keep mutating the live object,
        so anything handed to the MVCC store must be detached."""
        return TaskState(
            state=self.state, failed=self.failed, restarts=self.restarts,
            last_restart=self.last_restart, started_at=self.started_at,
            finished_at=self.finished_at, events=list(self.events),
        )


@dataclass(slots=True)
class NetworkStatus:
    interface_name: str = ""
    address: str = ""
    dns: Optional[dict] = None


@dataclass(slots=True)
class AllocatedPort:
    label: str = ""
    value: int = 0
    to: int = 0
    host_ip: str = ""


@dataclass(slots=True)
class Allocation:
    """A placement of a task group on a node (reference structs.go Allocation:10694).

    `allocated_vec` is the dense comparable resource total for this alloc
    (cpu, mem, disk) — the quantity the fit math and tensor cache consume.
    """

    id: str = ""
    eval_id: str = ""
    name: str = ""               # "<job>.<group>[<index>]"
    namespace: str = "default"
    node_id: str = ""
    node_name: str = ""
    job_id: str = ""
    job: object = None           # snapshot of the Job at placement time
    job_version: int = 0
    task_group: str = ""
    allocated_vec: np.ndarray = field(default_factory=lambda: comparable())
    allocated_ports: List[AllocatedPort] = field(default_factory=list)
    allocated_devices: Dict[str, List[str]] = field(default_factory=dict)  # device id -> instance ids
    allocated_cores: List[int] = field(default_factory=list)
    desired_status: str = enums.ALLOC_DESIRED_RUN
    desired_description: str = ""
    desired_transition: DesiredTransition = field(default_factory=DesiredTransition)
    client_status: str = enums.ALLOC_CLIENT_PENDING
    client_description: str = ""
    task_states: Dict[str, object] = field(default_factory=dict)
    deployment_id: str = ""
    deployment_status: Optional[dict] = None
    canary: bool = False
    previous_allocation: str = ""
    next_allocation: str = ""
    reschedule_tracker: Optional[RescheduleTracker] = None
    follow_up_eval_id: str = ""
    preempted_by_allocation: str = ""
    metrics: Optional[AllocMetric] = None
    allocated_at: float = 0.0
    # when the (last) task finished — drives reschedule eligibility
    # (reference: TaskStates[].FinishedAt consumed by NextRescheduleTime)
    task_finished_at: float = 0.0
    modify_time: float = 0.0
    create_index: int = 0
    modify_index: int = 0
    alloc_modify_index: int = 0

    # --- status predicates (reference structs.go Allocation.*TerminalStatus) ---

    def server_terminal(self) -> bool:
        return self.desired_status in (enums.ALLOC_DESIRED_STOP, enums.ALLOC_DESIRED_EVICT)

    def client_terminal(self) -> bool:
        return self.client_status in (
            enums.ALLOC_CLIENT_COMPLETE,
            enums.ALLOC_CLIENT_FAILED,
            enums.ALLOC_CLIENT_LOST,
        )

    def terminal_status(self) -> bool:
        """Either side says it's over (reference Allocation.TerminalStatus)."""
        return self.server_terminal() or self.client_terminal()

    def should_count_for_usage(self) -> bool:
        """Whether this alloc consumes node resources in fit math:
        client-terminal allocs are free (reference funcs.go:150-153
        AllocsFit skips ClientTerminalStatus)."""
        return not self.client_terminal()

    def migrate_disk(self) -> bool:
        if self.job is None:
            return False
        tg = self.job.lookup_task_group(self.task_group)
        return tg is not None and tg.ephemeral_disk.migrate

    def index(self) -> int:
        """Parse the bracketed index out of the alloc name
        (reference structs.go AllocName / AllocIndexFromName)."""
        l = self.name.rfind("[")
        r = self.name.rfind("]")
        if l == -1 or r == -1 or r <= l:
            return -1
        try:
            return int(self.name[l + 1:r])
        except ValueError:
            return -1

    def copy_for_update(self) -> "Allocation":
        """Shallow-ish copy used when mutating an alloc into a new raft
        generation (MVCC tables hold immutable-by-convention rows)."""
        import copy as _copy

        new = _copy.copy(self)
        new.desired_transition = _copy.copy(self.desired_transition)
        return new


def alloc_name(job_id: str, group: str, index: int) -> str:
    """Reference structs.AllocName format "<job>.<group>[<index>]"."""
    return f"{job_id}.{group}[{index}]"


# Block alloc id = "<block uuid>.<position>". The separator must be
# URL-safe (ids ride in /v1/allocation/<id> paths — "#" would be eaten
# as a fragment delimiter) and must not occur in uuids (hex + "-").
BLOCK_SEP = "."


@dataclass(slots=True)
class AllocBlock:
    """Columnar batch of K identical fresh placements of one task group
    (the C2M bulk-placement shape).

    The reference has no analog — its plan/state paths are one
    `Allocation` struct per placement end to end (structs.go
    Allocation:10694 flowing through plan_apply.go:96 and
    state_store.go:369 UpsertPlanResults). At 2M allocations that
    per-object host work dominates wall clock, so the bulk path carries
    placements as ONE record batch: per-node counts + shared columns.
    Individual `Allocation` rows materialize lazily (API reads, client
    sync) and are "promoted" to real MVCC rows on first write (client
    status update, stop) — the store overrides a block position with its
    promoted row wherever both are visible.

    Layout is frozen at plan time: `node_ids[m]` receives
    `counts[m]` placements; global position p (0..K-1) maps to node row
    via the counts prefix sums, alloc id `"{id}.{p}"`, and alloc name
    index `name_indices[p]`. Applier rejection drops whole node rows
    (`rejected_rows`) without renumbering; GC drops individual positions
    (`dropped`). Both only ever shrink the visible set, so materialized
    ids/names are stable for the block's lifetime.
    """

    id: str = ""
    eval_id: str = ""
    namespace: str = "default"
    job_id: str = ""
    job: object = None
    job_version: int = 0
    task_group: str = ""
    deployment_id: str = ""
    name_indices: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    node_ids: List[str] = field(default_factory=list)
    node_names: List[str] = field(default_factory=list)
    counts: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    allocated_vec: np.ndarray = field(default_factory=lambda: comparable())
    mean_score: float = 0.0
    # the per-placement scan's blocks: position p's own normalized score
    # (f32, as the kernel returned it) and the group's two node counts,
    # once; empty for the count solve's blocks, which keep mean_score
    scores: np.ndarray = field(default_factory=lambda: np.zeros(0, np.float32))
    nodes_evaluated: int = 0
    nodes_in_pool: int = 0
    allocated_at: float = 0.0
    modify_time: float = 0.0
    create_index: int = 0
    modify_index: int = 0
    # node rows the plan applier rejected (never committed)
    rejected_rows: frozenset = frozenset()
    # positions GC'd after their promoted rows went away
    dropped: frozenset = frozenset()
    # caches (never serialized; rebuilt lazily)
    _offsets: object = field(default=None, repr=False, compare=False)
    _mat: dict = field(default_factory=dict, repr=False, compare=False)
    _metrics: object = field(default=None, repr=False, compare=False)
    _rows_of: object = field(default=None, repr=False, compare=False)
    # the live node rows in the row numbers of the store's dense
    # per-node columns (core/plan_apply.py _BlockRows)
    _store_rows: object = field(default=None, repr=False, compare=False)

    def __deepcopy__(self, memo):
        import copy as _copy

        new = AllocBlock(
            id=self.id, eval_id=self.eval_id, namespace=self.namespace,
            job_id=self.job_id, job=_copy.deepcopy(self.job, memo),
            job_version=self.job_version, task_group=self.task_group,
            deployment_id=self.deployment_id,
            name_indices=self.name_indices.copy(),
            node_ids=list(self.node_ids), node_names=list(self.node_names),
            counts=self.counts.copy(),
            allocated_vec=self.allocated_vec.copy(),
            mean_score=self.mean_score, scores=self.scores.copy(),
            nodes_evaluated=self.nodes_evaluated,
            nodes_in_pool=self.nodes_in_pool,
            allocated_at=self.allocated_at,
            modify_time=self.modify_time, create_index=self.create_index,
            modify_index=self.modify_index,
            rejected_rows=self.rejected_rows, dropped=self.dropped,
        )
        return new

    # -- layout --

    @property
    def size(self) -> int:
        """Plan-time placement count (includes rejected/dropped)."""
        return len(self.name_indices)

    def offsets(self) -> np.ndarray:
        off = self._offsets
        if off is None:
            off = self._offsets = np.concatenate(
                [[0], np.cumsum(self.counts)]).astype(np.int64)
        return off

    def live_size(self) -> int:
        """Committed, un-GC'd placements."""
        n = self.size - len(self.dropped)
        if self.rejected_rows:
            off = self.offsets()
            for m in self.rejected_rows:
                lo, hi = int(off[m]), int(off[m + 1])
                n -= (hi - lo) - sum(1 for p in self.dropped if lo <= p < hi)
        return n

    def row_for_pos(self, p: int) -> int:
        return int(np.searchsorted(self.offsets(), p, side="right")) - 1

    def live_rows(self):
        return (m for m in range(len(self.node_ids))
                if m not in self.rejected_rows)

    def live_node_counts(self) -> tuple:
        """(node ids, counts) of the node rows the applier did not
        reject: the block as per-node columns, for readers that add a
        plan's placements up without materializing them."""
        if not self.rejected_rows:
            return self.node_ids, self.counts
        rows = list(self.live_rows())
        return [self.node_ids[m] for m in rows], self.counts[rows]

    def positions_for_row(self, m: int) -> range:
        off = self.offsets()
        return range(int(off[m]), int(off[m + 1]))

    def visible(self, p: int) -> bool:
        if p in self.dropped:
            return False
        return self.row_for_pos(p) not in self.rejected_rows

    # -- materialization --

    def _metrics_at(self, p: int, node_id: str):
        """The AllocMetric the per-placement row path wrote for position
        p where the block carries per-position scores; else the one
        shared bulk metric."""
        if len(self.scores):
            return AllocMetric(
                nodes_evaluated=self.nodes_evaluated,
                nodes_in_pool=self.nodes_in_pool,
                scores={f"{node_id}.normalized-score": float(self.scores[p])})
        metrics = self._metrics
        if metrics is None:
            metrics = self._metrics = AllocMetric(
                scores={"bulk.normalized-score": self.mean_score})
        return metrics

    def alloc_at(self, p: int) -> "Allocation":
        """Materialize position p (cached; the cache holds plain
        snapshot-shaped rows — writers must copy_for_update like any
        other MVCC row)."""
        a = self._mat.get(p)
        if a is None:
            m = self.row_for_pos(p)
            a = self._mat[p] = Allocation(
                id=f"{self.id}{BLOCK_SEP}{p}",
                eval_id=self.eval_id,
                name=alloc_name(self.job_id, self.task_group,
                                int(self.name_indices[p])),
                namespace=self.namespace,
                node_id=self.node_ids[m],
                node_name=self.node_names[m] if self.node_names else "",
                job_id=self.job_id,
                job=self.job,
                job_version=self.job_version,
                task_group=self.task_group,
                deployment_id=self.deployment_id,
                allocated_vec=self.allocated_vec,
                metrics=self._metrics_at(p, self.node_ids[m]),
                allocated_at=self.allocated_at,
                modify_time=self.modify_time,
                create_index=self.create_index,
                modify_index=self.modify_index,
            )
        return a

    def allocs_for_row(self, m: int) -> List["Allocation"]:
        if m in self.rejected_rows:
            return []
        return [self.alloc_at(p) for p in self.positions_for_row(m)
                if p not in self.dropped]

    def rows_for_node(self, node_id: str) -> Sequence[int]:
        """Node rows of `node_id` (layout is frozen at plan time, so the
        index is built once)."""
        rows_of = self._rows_of
        if rows_of is None:
            rows_of = {}
            for m, nid in enumerate(self.node_ids):
                rows_of.setdefault(nid, []).append(m)
            self._rows_of = rows_of
        return rows_of.get(node_id, ())

    def allocs_for_node(self, node_id: str) -> List["Allocation"]:
        out: List[Allocation] = []
        for m in self.rows_for_node(node_id):
            out.extend(self.allocs_for_row(m))
        return out

    def iter_allocs(self):
        for m in self.live_rows():
            yield from self.allocs_for_row(m)

    # -- applier slicing / GC --

    def without_nodes(self, bad_node_ids) -> "AllocBlock":
        """Copy with the given nodes' rows marked rejected (plan applier
        partial commit). Positions/ids stay stable."""
        import copy as _copy

        bad = set(bad_node_ids)
        rows = {m for m, nid in enumerate(self.node_ids) if nid in bad}
        new = _copy.copy(self)
        new.rejected_rows = self.rejected_rows | rows
        new._offsets = self._offsets
        new._mat = {}
        new._metrics = None
        new._store_rows = None
        return new

    def with_dropped(self, positions) -> "AllocBlock":
        import copy as _copy

        new = _copy.copy(self)
        new.dropped = self.dropped | set(positions)
        new._offsets = self._offsets
        new._mat = {}
        new._metrics = None
        return new
