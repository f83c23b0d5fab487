"""Tensorization: snapshot + in-progress plan -> dense arrays.

The piece with no reference analog (SURVEY.md §7 stage 2): lowers the
object-graph view the host scheduler walks (nodes, proposed allocs,
constraints, spreads) into the padded arrays kernels.py consumes.

Constraint semantics stay host-side — regex/version/semver operators are
evaluated once per *unique attribute value* by the vectorized masks in
scheduler.feasible (the tensor-era form of the reference's computed-node-
class memoization, context.go:261) — and only the resulting boolean masks
and interned value-id tables ship to the device.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from itertools import repeat
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import TRACER
from ..structs import Job, Node, TaskGroup, enums
from ..structs.resources import RESOURCE_DIMS
from ..scheduler.context import EvalContext
from ..scheduler.feasible import (
    check_constraint,
    distinct_hosts_flags,
    feasible_mask,
    feasible_mask_static,
    csi_volume_mask,
    reserved_ports_mask,
    resolve_target,
    tg_mask_signature,
)
from ..scheduler.spread import IMPLICIT_TARGET, SpreadInfo, combined_spreads
from .incremental import feed_for
from .overlay import INFLIGHT


def _pad_pow2(n: int, floor: int = 8) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


class NodeSlotRegistry:
    """Stable node→slot assignment with a free-list, one per store: a
    node keeps its slot for as long as it exists, a deleted node's slot
    is recycled to the next joiner (lowest free slot first, so the slot
    space stays dense under churn). The incremental feed keys its
    epochs on row LAYOUT — today's statics still order rows by the
    dense ready-list, so membership changes resync — but the registry
    pins the identity the resync path and the join/leave tests reason
    about, and is the anchor for the layout-stable statics stretch
    (ROADMAP): a static ordering rows by slot would keep epochs alive
    across joins/leaves entirely."""

    def __init__(self):
        self._slots: Dict[str, int] = {}
        self._free: List[int] = []
        self._next = 0
        self._lock = threading.Lock()

    def assign(self, node_ids: Sequence[str], store=None) -> Dict[str, int]:
        """Slot per node id, allocating for new ids. When `store` is
        given, slots of nodes deleted from it are released first (the
        one authoritative leave signal; drained-but-present nodes keep
        their slot)."""
        import heapq

        with self._lock:
            if store is not None:
                for nid in [n for n in self._slots
                            if store._nodes.get_latest(n) is None]:
                    heapq.heappush(self._free, self._slots.pop(nid))
            out: Dict[str, int] = {}
            for nid in node_ids:
                s = self._slots.get(nid)
                if s is None:
                    if self._free:
                        s = heapq.heappop(self._free)
                    else:
                        s = self._next
                        self._next += 1
                    self._slots[nid] = s
                out[nid] = s
            return out

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"assigned": len(self._slots), "free": len(self._free),
                    "high_water": self._next}


class ClusterStatic:
    """Canonical per-(node-set version, node list) arrays shared across
    evals AND scheduler workers: everything here depends only on node
    identity/attributes — capacity, index maps, feasibility masks,
    affinity vectors, attribute-value interning — never on usage or
    plans. Keyed by the store's node_set_version; one node write anywhere
    invalidates the whole set.

    This is the round-4 resident layer: round 3 rebuilt every one of
    these O(nodes) Python-side arrays once per eval, which dominated the
    eval hot path at 10K nodes."""

    __slots__ = ("nodes", "n_pad", "available", "node_index", "usage_rows",
                 "version", "mask_cache", "aff_cache", "intern_cache",
                 "dev_cache", "device_arrays", "slots", "placed_zero")

    def __init__(self, nodes: Sequence[Node], store=None, version=None):
        n = len(nodes)
        self.nodes = list(nodes)
        self.n_pad = _pad_pow2(n)
        self.version = version
        self.available = np.zeros((self.n_pad, RESOURCE_DIMS))
        self.node_index: Dict[str, int] = {}
        for i, node in enumerate(nodes):
            self.available[i] = node.available_vec()
            self.node_index[node.id] = i
        self.usage_rows = (store.usage_rows_for([n.id for n in nodes])
                           if store is not None and n else None)
        # stable per-store node→slot identity (see NodeSlotRegistry);
        # None for uncached per-eval statics with no store behind them
        self.slots = None
        self.mask_cache: Dict[tuple, np.ndarray] = {}
        self.aff_cache: Dict[tuple, np.ndarray] = {}
        self.intern_cache: Dict[tuple, tuple] = {}
        self.dev_cache: Dict[tuple, tuple] = {}
        # device-RESIDENT copies of static arrays (capacity, masks,
        # affinity vectors, interned attribute rows, zero placement
        # counts), uploaded once per node-set version so a solve ships
        # only what its evaluation fixes (tensor/solver.py
        # ensure_resident; kernels.solve_task_group_fused and the solver
        # service's kernels.solve_bulk_multi)
        self.device_arrays: Dict = {}
        # the placement counts of a job with none on these nodes: one
        # read-only instance, whose identity says "resident zeros"
        self.placed_zero = np.zeros(self.n_pad, dtype=np.int32)
        self.placed_zero.setflags(write=False)


# one build at a time cluster-wide: builds are keyed per (version, node
# set) and idempotent, so a global lock (not per-store) is fine
_static_build_lock = threading.Lock()


def _static_for(ctx: EvalContext, nodes: Sequence[Node]):
    """Cached ClusterStatic when `nodes` is the canonical ready-node list
    (see StateSnapshot.ready_nodes_in_pool); None otherwise."""
    store = getattr(ctx.snapshot, "_store", None)
    if store is None:
        return None
    version = getattr(nodes, "canonical_version", None)
    if version is None or version != store.node_set_version:
        return None
    statics = getattr(store, "_tensor_statics", None)
    if statics is None:
        statics = store._tensor_statics = {}
    key = (version, getattr(nodes, "canonical_key", None))
    static = statics.get(key)
    if static is None:
        # serialize the (expensive, O(nodes)) build so N workers racing
        # on the same key share ONE ClusterStatic instead of each
        # building a duplicate — with batched eval processing every
        # worker hits this on the same version at once
        with _static_build_lock:
            static = statics.get(key)
            if static is None:
                # drop stale versions (iterate a keys copy — readers are
                # concurrent)
                for k in [k for k in list(statics) if k[0] != version]:
                    statics.pop(k, None)
                static = ClusterStatic(nodes, store=store, version=version)
                registry = getattr(store, "_node_slots", None)
                if registry is None:
                    registry = store._node_slots = NodeSlotRegistry()
                static.slots = registry.assign(
                    [n.id for n in static.nodes], store=store)
                statics[key] = static
    return static


@dataclass
class ClusterTensors:
    """Per-eval view: shared ClusterStatic + this eval's usage state."""

    nodes: List[Node]
    n_pad: int
    available: np.ndarray          # (Np, D) shared with the static — read-only
    used: np.ndarray               # (Np, D) proposed usage, per-eval
    node_index: Dict[str, int]
    static: "ClusterStatic" = None
    _store: object = None
    # `used` is the incremental feed's shared read-only base (zero-copy
    # warm path); any write path must go through _ensure_private first
    _used_shared: bool = False

    @classmethod
    def build(cls, ctx: EvalContext, nodes: Sequence[Node]) -> "ClusterTensors":
        static = _static_for(ctx, nodes)
        if static is None:
            static = ClusterStatic(nodes)  # per-eval, uncached
        t = cls(nodes=static.nodes, n_pad=static.n_pad,
                available=static.available, used=None,
                node_index=static.node_index, static=static,
                _store=getattr(ctx.snapshot, "_store", None))
        t.refresh_usage(ctx)
        return t

    def _ensure_private(self) -> np.ndarray:
        """A privately-owned writable `used` of the right shape —
        allocates on first use, copies the shared feed base out of the
        way, reuses an existing private buffer otherwise."""
        u = self.used
        if u is None or u.shape[0] != self.n_pad:
            u = self.used = np.zeros((self.n_pad, RESOURCE_DIMS))
        elif self._used_shared or not u.flags.writeable:
            u = self.used = u.astype(np.float64, copy=True)
        self._used_shared = False
        return u

    def refresh_usage(self, ctx: EvalContext,
                      out: Optional[np.ndarray] = None) -> None:
        """Proposed usage (state - evictions + placements). Base usage is
        one fancy-index gather from the store's dense usage matrix when
        available (latest-committed state: fresher than the snapshot,
        which only helps an optimistic solve — the serialized applier
        re-verifies), else O(nodes) snapshot rows. Only nodes the
        in-progress plan touches are recomputed from ctx.proposed_allocs
        (reference context.go:176 ProposedAllocs); the plan's AllocBlocks
        are added a block at a time. Called between task groups so
        group B sees group A's in-plan placements.

        `out` is the per-placement tier's gather under
        _PER_EVAL_SOLVE_LOCK: a caller-owned (n_pad, D) f32 buffer,
        allocated before the lock, filled here in one cast copy and
        shipped as it is; it becomes `used` for every later reader.
        Resource vectors are whole MHz / MB / port counts, exact in f32
        below 2**24 (latest_usage folds in f32 on the same ground);
        a plan-touched row is still summed in f64 and cast once."""
        snap = ctx.snapshot
        n = len(self.nodes)
        plan = ctx.plan
        touched = ()
        if plan is not None and (plan.node_update or plan.node_preemptions
                                 or plan.node_allocation or plan.preempted):
            touched = (set(plan.node_update) | set(plan.node_preemptions)
                       | set(plan.node_allocation)
                       | set(plan.preempted.node_ids if plan.preempted
                             else ()))
        blocks = plan.alloc_blocks if plan is not None else ()
        # incremental fast path (tensor/incremental.py): the feed's
        # delta-fed base already IS latest-committed usage in this
        # static's row order. With no plan-touched rows and no racing
        # in-flight placements the base is handed out as a shared
        # read-only view — the O(N) gather disappears entirely from the
        # warm path; otherwise it seeds a copy-on-write private buffer.
        #
        # Other racing evals' in-flight placements are read first, the
        # committed usage after them: see InflightOverlay.open_entries.
        inflight = INFLIGHT.open_entries(exclude_plan=ctx.plan)
        base = None
        if self._store is not None and self.static is not None:
            feed = feed_for(self._store)
            if feed is not None:
                base = feed.base_for(self.static)
        if out is not None:
            self.used, self._used_shared = out, False
        if base is not None:
            if out is not None:
                np.copyto(out, base)
                used = out
            elif not touched and not inflight and not blocks:
                self.used = base
                self._used_shared = True
                return
            else:
                used = self.used = base.copy()
                self._used_shared = False
        else:
            used = self._ensure_private()
            rows = (self.static.usage_rows if self.static is not None
                    else None)
            if rows is not None and self._store is not None:
                used[:n] = self._store._usage_mat[rows]
                used[n:] = 0.0
            else:
                used[:] = 0.0
                for i, node in enumerate(self.nodes):
                    u = snap.node_usage(node.id)
                    if u is not None:
                        used[i] = u
        if plan is not None:
            for node_id in touched:
                i = self.node_index.get(node_id)
                if i is None:
                    continue
                row = np.zeros(RESOURCE_DIMS)
                for a in ctx.proposed_allocs(node_id):
                    if a.should_count_for_usage():
                        row += a.allocated_vec
                used[i] = row
        # the plan's columnar placements (an earlier group's block):
        # indexed adds a block, but for a touched node, whose row above
        # was summed from proposed_allocs and holds them already
        for block in blocks:
            rows, counts = self._block_rows(block, skip=touched)
            np.add.at(used, rows,
                      counts[:, None] * block.allocated_vec[None, :])
        # other racing evals' in-flight (solved, not yet committed)
        # placements: fold LAST so this solve plans around them instead
        # of colliding on the same best-fit nodes (tensor/overlay.py;
        # the per-eval twin of the bulk solver service's carry)
        INFLIGHT.fold(used[:n], self.node_index, entries=inflight)

    def _block_rows(self, block, skip=()) -> Tuple[np.ndarray, np.ndarray]:
        """(row indices, counts) of a plan block's live node rows in
        this cluster's order; nodes in `skip` or not in the order are
        left out."""
        ids, counts = block.live_node_counts()
        index = self.node_index
        rows = np.fromiter(
            (-1 if nid in skip else index.get(nid, -1) for nid in ids),
            np.int64, len(ids))
        keep = rows >= 0
        return rows[keep], np.asarray(counts, np.int64)[keep]

    def latest_usage(self) -> np.ndarray:
        """Freshly-gathered LATEST committed usage, (n_pad, D) float32.
        The bulk solver service calls this at RESYNC time (not solve
        time): a resync base captured when the eval started can be
        seconds stale under queue depth, and usage committed by solves
        whose ledger entries already closed would be lost from the
        carry — the round-5 oversubscription cascade."""
        rows = self.static.usage_rows if self.static is not None else None
        if rows is not None and self._store is not None:
            # read before the committed usage, as in refresh_usage
            inflight = INFLIGHT.open_entries()
            mat = self._store._usage_mat  # local ref: matrix may be
            # swapped by a concurrent restore (_rebuild_usage_matrix);
            # row assignments may then be stale — bounds-check and fall
            # back, the applier re-verifies either way
            if len(rows) == 0 or rows.max() < mat.shape[0]:
                out = np.zeros((self.n_pad, RESOURCE_DIMS), dtype=np.float32)
                out[: len(self.nodes)] = mat[rows]
                # per-eval in-flight placements (tensor/overlay.py) are
                # not in the store yet NOR in the service's own ledger —
                # fold them so a bulk resync can't double-book against
                # racing spread/constraint evals
                INFLIGHT.fold(out[: len(self.nodes)], self.node_index,
                              entries=inflight)
                return out
        return self.used.astype(np.float32)

    def placement_counts(self, job: Job, tg: TaskGroup,
                         ctx: EvalContext) -> Tuple[np.ndarray, np.ndarray]:
        """(placed_tg, placed_job) int32 vectors counting this job's
        proposed allocs per node (anti-affinity + distinct_hosts inputs).
        Walks only this job's allocs plus the plan — not every alloc.
        A job with none on these nodes gets the static's read-only
        `placed_zero` for both, whose device copy is resident."""
        ptg = np.zeros(self.n_pad, dtype=np.int32)
        pjob = np.zeros(self.n_pad, dtype=np.int32)
        found = False
        plan = ctx.plan
        removed: set = set()
        placed_ids: set = set()
        if plan is not None:
            for allocs in plan.node_update.values():
                removed.update(a.id for a in allocs)
            for allocs in plan.node_preemptions.values():
                removed.update(a.id for a in allocs)
            for allocs in plan.node_allocation.values():
                placed_ids.update(a.id for a in allocs)
        for a in ctx.snapshot.allocs_by_job(job.id, job.namespace):
            if a.terminal_status() or a.id in removed or a.id in placed_ids:
                continue
            i = self.node_index.get(a.node_id)
            if i is None:
                continue
            found = True
            pjob[i] += 1
            if a.task_group == tg.name:
                ptg[i] += 1
        if plan is not None:
            for node_id, allocs in plan.node_allocation.items():
                i = self.node_index.get(node_id)
                if i is None:
                    continue
                for a in allocs:
                    if a.job_id != job.id or a.namespace != job.namespace:
                        continue
                    found = True
                    pjob[i] += 1
                    if a.task_group == tg.name:
                        ptg[i] += 1
            for block in plan.alloc_blocks:
                if (block.job_id != job.id
                        or block.namespace != job.namespace):
                    continue
                found = True
                rows, counts = self._block_rows(block)
                np.add.at(pjob, rows, counts)
                if block.task_group == tg.name:
                    np.add.at(ptg, rows, counts)
        if not found and self.static is not None:
            return self.static.placed_zero, self.static.placed_zero
        return ptg, pjob


@dataclass
class VictimTensors:
    """Per-node victim columns for the in-kernel preemption solve
    (kernels.preempt_solve): every eligible lower-priority alloc on a
    node becomes a column slot carrying its priority, allocated
    resource vector, eligibility, and an exact-resource flag
    (port/device holders the dense columns can't model — rows whose
    victim set touches one fall back to the exact host scanner).

    Built per (eval, task-group priority) snapshot — eligibility
    depends on the in-progress plan's proposed allocs, so unlike
    ClusterStatic these are NOT cacheable across evals. Column order is
    scheduler.preemption.victim_candidates' canonical order (priority
    asc, alloc id asc), which is exactly the prefix order the kernel
    consumes; `refs[i][v]` maps column v of node i back to the concrete
    Allocation. v_pad quantizes to powers of two (same G_PAD/K_PAD
    discipline as the solver service) so the production shape compiles
    once at warmup."""

    v_pad: int
    prio: np.ndarray       # (Np, V) f32, 0 on empty slots
    vec: np.ndarray        # (Np, V, D) f32 allocated resource vectors
    elig: np.ndarray       # (Np, V) bool
    flagged: np.ndarray    # (Np, V) bool port/device holders
    refs: List[List]       # per real node, column order
    evictable: np.ndarray  # (Np, D) f32 sum of eligible victim vectors
    net_prio: np.ndarray   # (Np,) f32 aggregate max + sum/max


def build_victim_tensors(ctx: EvalContext, cluster: "ClusterTensors",
                         current_priority: int,
                         v_floor: int = 8) -> VictimTensors:
    """Lower every node's preemptible-alloc set into padded victim
    columns + the per-node aggregates (evictable capacity, approximate
    netPriority) the node-choice score consumes. One pass over proposed
    allocs per node — this replaces the Python aggregate loops the old
    host preemption path re-ran per batch."""
    from ..scheduler.preemption import (victim_candidates,
                                        victim_holds_exact_resources)

    nodes = cluster.nodes
    n_pad = cluster.n_pad
    d = cluster.available.shape[1]
    per_node = [victim_candidates(ctx.proposed_allocs(node.id),
                                  current_priority) for node in nodes]
    v_max = max((len(c) for c in per_node), default=0)
    v_pad = _pad_pow2(max(v_max, 1), floor=v_floor)

    prio = np.zeros((n_pad, v_pad), dtype=np.float32)
    vec = np.zeros((n_pad, v_pad, d), dtype=np.float32)
    elig = np.zeros((n_pad, v_pad), dtype=bool)
    flagged = np.zeros((n_pad, v_pad), dtype=bool)
    max_p = np.zeros(n_pad, dtype=np.float32)
    sum_p = np.zeros(n_pad, dtype=np.float32)
    for i, cands in enumerate(per_node):
        for v, a in enumerate(cands):
            p = float(a.job.priority)
            prio[i, v] = p
            vec[i, v] = np.asarray(a.allocated_vec[:d], dtype=np.float32)
            elig[i, v] = True
            flagged[i, v] = victim_holds_exact_resources(a)
            sum_p[i] += p
            if p > max_p[i]:
                max_p[i] = p
    evictable = (vec * elig[:, :, None]).sum(axis=1)
    net_prio = np.where(max_p > 0,
                        max_p + sum_p / np.maximum(max_p, 1.0),
                        0.0).astype(np.float32)
    return VictimTensors(v_pad=v_pad, prio=prio, vec=vec, elig=elig,
                         flagged=flagged, refs=per_node,
                         evictable=evictable, net_prio=net_prio)


class SystemVictims:
    """A system group's victim columns (kernels.system_place): per node
    row, one column a preemptible allocation of the node as the plan
    leaves it, in (priority, alloc id) order, which is the exact host
    arm's candidate order (scheduler/preemption.py victim_candidates).
    Host arrays (`Np` node rows, `V` columns). `holds` says which rows
    carry a live allocation with a port number (any allocation,
    preemptible or not): the port assignment of a row without one and
    without reserved ports is the lowest numbers of its dynamic range.
    Which allocation and which group a column is, is resolved on demand
    (`ids_at`, `groups_at`): a block position's id only for the columns
    asked about. `kept` counts the rows read from the store's kept
    columns rather than walked."""

    def __init__(self, v_pad, prio, vec, valid, flagged, mp, grp, cnt0,
                 holds, names, n_victims, kept):
        self.v_pad = v_pad
        self.prio = prio        # (Np, V) priority, 0 on empty columns
        self.vec = vec          # (Np, V, D) allocated_vec
        self.valid = valid      # (Np, V) bool: a preemptible allocation
        self.flagged = flagged  # (Np, V) bool: holds ports or devices
        self.mp = mp            # (Np, V) its group's migrate max_parallel
        self.grp = grp          # (Np, V) int32 first column of its group
        self.cnt0 = cnt0        # (Np, V) its group's evictions in the plan
        self.holds = holds      # (Np,) bool
        self._names = names
        self.n_victims = n_victims
        self.kept = kept

    def ids_at(self, rows, cols) -> List[str]:
        """The allocation id of each column (rows[i], cols[i])."""
        return self._names.ids(np.asarray(rows), np.asarray(cols))

    def groups_at(self, rows, cols) -> List[tuple]:
        """(namespace, job id, task group) of each column."""
        return self._names.groups(np.asarray(rows), np.asarray(cols))


class _ColumnNames:
    """Who each victim column is: `src[r, v]` indexes `refs`, an
    allocation id, or a block id whose node row is `sub` (-1 for an
    allocation row), the column then being that node row's `off[r,
    v]`-th live position in id order at the snapshot; `gid[r, v]`
    indexes `keys`, the groups."""

    def __init__(self, snap, src, off, gid, refs, sub, keys):
        self.snap = snap
        self.src, self.off, self.gid = src, off, gid
        self.refs, self.sub, self.keys = refs, sub, keys
        self._positions: Dict[tuple, List[str]] = {}

    def ids(self, rows, cols) -> List[str]:
        src = self.src[rows, cols]
        out = self.refs[src].tolist()
        for i in np.flatnonzero(self.sub[src] >= 0).tolist():
            names = self._positions_of(out[i], int(self.sub[src[i]]))
            out[i] = names[int(self.off[rows[i], cols[i]])]
        return out

    def groups(self, rows, cols) -> List[tuple]:
        keys = self.keys
        return [keys[g] for g in self.gid[rows, cols].tolist()]

    def _positions_of(self, block_id: str, m: int) -> List[str]:
        """The ids of node row m's positions that are live and no row
        shadows, in id order (what the store's slot for the row counts)."""
        got = self._positions.get((block_id, m))
        if got is None:
            from ..structs.alloc import BLOCK_SEP

            store, index = self.snap._store, self.snap.index
            block = store._alloc_blocks.get(block_id, index)
            prefix = block_id + BLOCK_SEP
            rows = store._allocs
            got = self._positions[(block_id, m)] = sorted(
                prefix + str(p) for p in block.positions_for_row(m)
                if p not in block.dropped
                and rows.get(prefix + str(p), index) is None)
        return got


class _Kept:
    """What _read_kept gathered: `ok` the node rows it read, `holds`
    those holding a port number; one entry a column, in column order
    (by node, then candidate order): `node`, `col`, `src` (its slot
    among the gathered ones, whose `prio`, `vec`, `flag`, `mp`, `gid`
    (into `keys`), `ref`, `sub` it takes) and `off` (its place among
    its block row's positions)."""

    def __init__(self, n: int):
        self.ok = np.zeros(n, dtype=bool)
        self.holds = np.zeros(0, np.int64)
        self.node = self.col = self.src = self.off = np.zeros(0, np.int64)
        self.prio = self.mp = self.gid = self.sub = np.zeros(0, np.int64)
        self.vec = np.zeros((0, RESOURCE_DIMS))
        self.flag = np.zeros(0, dtype=bool)
        self.ref = np.zeros(0, dtype=object)
        self.keys: List[tuple] = []


def _stamps(cols, rows: np.ndarray) -> np.ndarray:
    stamp = cols.stamp
    out = np.full(len(rows), cols.base, np.int64)
    inside = (rows >= 0) & (rows < len(stamp))
    out[inside] = stamp[rows[inside]]
    return out


def _read_kept(snap, cluster, touched: set, top: int) -> _Kept:
    """The victim columns of the nodes the store's kept allocation
    columns (state/alloc_columns.py) hold as the snapshot sees them: no
    write to the node's slots since the snapshot's index, read without a
    lock (a stamp check before and after the gathers), and the plan
    leaves the node alone. A node whose block row sits beside another
    slot of the same block (a row of one of its positions, which sorts
    inside it) is left out too: every other node's slots sort as
    (priority, id) by their keys, the few equal keys by their ids."""
    store, index = snap._store, snap.index
    nodes = cluster.nodes
    n = len(nodes)
    out = _Kept(n)
    cols = store.alloc_columns()
    assignment = cols.assignment
    n_rows = len(assignment.ids)
    static = cluster.static
    # the node set's rows, once a node-set version (a restore is a new
    # version; and every row of columns built since stamps past it)
    rows = (static.usage_rows if static is not None
            and static.usage_rows is not None else
            np.fromiter(map(assignment.get, (nd.id for nd in nodes),
                            repeat(-1)), np.int64, n))
    ok = (rows >= 0) & (rows < n_rows)
    for nid in touched:
        i = cluster.node_index.get(nid)
        if i is not None:
            ok[i] = False
    ok &= _stamps(cols, rows) <= index
    top_slot = cols.top
    node = cols.node[:top_slot]
    count, prio = cols.count[:top_slot], cols.prio[:top_slot]
    inv = np.full(n_rows + 1, -1, np.int64)
    inv[rows[ok]] = np.flatnonzero(ok)
    ci = inv[np.where((node >= 0) & (node < n_rows), node, n_rows)]
    live = np.flatnonzero((ci >= 0) & (count > 0))
    holds = ci[live[cols.ports[:top_slot][live]]]
    el = live[prio[live] <= top]
    c_ci, c_count, c_prio = ci[el], count[el], prio[el]
    c_vec, c_flag = cols.vec[el], cols.flag[el]
    c_mp, c_key, c_unit = cols.mp[el], cols.key[el], cols.unit[el]
    c_ref, c_sub = cols.ref[el], cols.sub[el]
    c_grp = cols.grp[el]
    local = np.zeros(len(cols.groups), np.int64)   # groups only grow
    local[c_grp] = 1
    gids = np.flatnonzero(local)
    local[gids] = np.arange(len(gids))
    c_gid = local[c_grp]
    keys = [cols.groups[g] for g in gids.tolist()]
    if store._alloc_cols is not cols:
        return out   # a restore: every node is walked
    ok &= _stamps(cols, rows) <= index
    ok[_mixed(c_ci, c_unit, c_sub)] = False
    keep = ok[c_ci]
    c_ci, c_count, c_prio, c_vec, c_flag, c_mp, c_key, c_ref, c_sub, \
        c_gid = (x[keep] for x in (c_ci, c_count, c_prio, c_vec, c_flag,
                                   c_mp, c_key, c_ref, c_sub, c_gid))
    out.ok, out.holds = ok, holds[ok[holds]]
    order = _candidate_order(c_ci, c_prio, c_key, c_ref, c_sub, n)
    cnt = c_count[order]
    total = int(cnt.sum())
    src = np.repeat(order, cnt)
    out.node = c_ci[src]
    step = np.arange(total)
    first = np.flatnonzero(np.r_[True, out.node[1:] != out.node[:-1]]) \
        if total else np.zeros(0, np.int64)
    out.col = step - np.repeat(first, np.diff(np.r_[first, total]))
    out.off = step - np.repeat(np.cumsum(cnt) - cnt, cnt)
    out.src = src
    out.prio, out.vec, out.flag, out.mp = c_prio, c_vec, c_flag, c_mp
    out.gid, out.ref, out.sub, out.keys = c_gid, c_ref, c_sub, keys
    return out


def _mixed(ci, unit, sub) -> np.ndarray:
    """Node indexes where a block's row slot has another slot of the
    same block beside it (a row of one of its positions, or a second
    row of the block): there the block does not sort as one unit."""
    comb = (ci.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) \
        ^ unit.astype(np.uint64)
    blk = sub >= 0
    rows = ~blk & (unit != 0)
    b = comb[blk]
    uniq, counts = np.unique(b, return_counts=True)
    return np.union1d(ci[rows][np.isin(comb[rows], b)],
                      ci[blk][np.isin(b, uniq[counts > 1])])


def _candidate_order(ci, prio, key, ref, sub, n: int) -> np.ndarray:
    """The slots in column order: by node, then (priority, id), the
    host arm's candidate order. One sort of a packed (node, priority,
    key) number where the widths fit, else a lexsort; slots whose keys
    tie are put in order by their ids (a block's row by its prefix)."""
    from ..structs.alloc import BLOCK_SEP

    if not len(ci):
        return np.zeros(0, np.int64)
    pmin = int(prio.min())
    pb = int(int(prio.max()) - pmin).bit_length()
    kb = 64 - max(int(n - 1).bit_length(), 1) - pb
    if kb >= 16:
        short = key >> np.uint64(64 - kb)
        packed = ((ci.astype(np.uint64) << np.uint64(pb + kb))
                  | ((prio - pmin).astype(np.uint64) << np.uint64(kb))
                  | short)
        order = np.argsort(packed, kind="quicksort")
        ranked = packed[order]
        same = ranked[1:] == ranked[:-1]
    else:
        order = np.lexsort((key, prio, ci))
        a, b = order[1:], order[:-1]
        same = (ci[a] == ci[b]) & (prio[a] == prio[b]) & (key[a] == key[b])
    starts = np.flatnonzero(same & ~np.r_[False, same[:-1]])
    for st in starts.tolist():
        end = st
        while end < len(same) and same[end]:
            end += 1
        run = order[st:end + 1]
        names = [ref[j] + BLOCK_SEP if sub[j] >= 0 else ref[j]
                 for j in run.tolist()]
        order[st:end + 1] = run[sorted(range(len(run)),
                                       key=names.__getitem__)]
    return order


def _plan_nodes(plan) -> set:
    """The nodes an in-progress plan touches."""
    touched: set = set()
    if plan is not None:
        touched.update(plan.node_update, plan.node_preemptions,
                       plan.node_allocation)
        if plan.preempted:
            touched.update(plan.preempted.node_ids)
        for block in plan.alloc_blocks:
            touched.update(block.live_node_counts()[0])
    return touched


def _walk_node(ctx, node_id: str, touched: set, top: int,
               current_priority: int):
    """One node's candidates read from the snapshot's index entries
    without materialising a block position: a row as it is, a block's
    node row position by position (a position promoted to a row is that
    row, a GC'd one is gone); a node the plan touches from
    ctx.proposed_allocs. -> ([(priority, id, allocated_vec, flagged,
    group, max_parallel)] in candidate order, holds a port number)."""
    from ..scheduler.preemption import (max_parallel_of, victim_candidates,
                                        victim_holds_exact_resources)
    from ..state.store import BlockRef
    from ..structs.alloc import BLOCK_SEP

    def group(a) -> tuple:
        return (a.namespace, a.job_id, a.task_group)

    def cand(a) -> tuple:
        return (a.job.priority, a.id, a.allocated_vec,
                victim_holds_exact_resources(a), group(a),
                max_parallel_of(a.job, a.task_group))

    if node_id in touched:
        proposed = ctx.proposed_allocs(node_id)
        holds = any(a.allocated_ports for a in proposed
                    if a.should_count_for_usage())
        return ([cand(a) for a in victim_candidates(proposed,
                                                    current_priority)],
                holds)
    snap = ctx.snapshot
    store, index = snap._store, snap.index
    rows_t, blocks_t = store._allocs, store._alloc_blocks
    out: list = []
    holds = False

    def from_row(a) -> None:
        nonlocal holds
        if a.allocated_ports:
            holds = True
        if (a.job is not None and a.job.priority <= top
                and a.should_count_for_usage()):
            out.append(cand(a))

    for entry in snap._ids_from_index(store._allocs_by_node, node_id):
        if type(entry) is not BlockRef:
            a = rows_t.get(entry, index)
            if a is not None and not a.terminal_status():
                from_row(a)
            continue
        block = blocks_t.get(entry.block_id, index)
        if block is None or entry.row in block.rejected_rows:
            continue
        job = block.job
        ported = block.ports is not None
        # a victim that holds ports or devices: the host scorer's node
        flagged = ported or block.devices is not None
        wanted = job is not None and job.priority <= top
        if not wanted and not ported:
            continue
        prefix = block.id + BLOCK_SEP
        key = (block.namespace, block.job_id, block.task_group)
        mp = max_parallel_of(job, block.task_group) if wanted else 0
        for p in block.positions_for_row(entry.row):
            if p in block.dropped:
                continue
            aid = prefix + str(p)
            row = rows_t.get(aid, index)
            if row is not None:
                if not row.terminal_status():
                    from_row(row)
                continue
            if ported:
                holds = True
            if wanted:
                out.append((job.priority, aid, block.allocated_vec,
                            flagged, key, mp))
    out.sort(key=lambda c: (c[0], c[1]))
    return out, holds


def build_system_victims(ctx: EvalContext, cluster: "ClusterTensors",
                         current_priority: int,
                         v_floor: int = 8) -> SystemVictims:
    """The victim columns of every node of `cluster`. A node no write
    has touched since the evaluation's snapshot, and which the
    in-progress plan leaves alone, is read from the store's kept
    per-node allocation columns with a few array operations
    (_read_kept); every other node is walked from the snapshot's index
    entries (_walk_node). Eligibility is preemption.is_preemptible's:
    priority at least PRIORITY_DELTA under `current_priority`, not
    terminal, counting for usage; max_parallel is the victim's own
    job's, as the host arm reads it."""
    from ..scheduler.preemption import PRIORITY_DELTA, plan_preempted_counts

    plan = ctx.plan
    touched = _plan_nodes(plan)
    top = current_priority - PRIORITY_DELTA
    nodes = cluster.nodes
    kept = _read_kept(ctx.snapshot, cluster, touched, top)
    walked = {i: _walk_node(ctx, nodes[i].id, touched, top,
                            current_priority)
              for i in np.flatnonzero(~kept.ok).tolist()}
    n_pad, d = cluster.n_pad, cluster.available.shape[1]
    v_max = max([int(kept.col.max()) + 1 if len(kept.col) else 0]
                + [len(c) for c, _ in walked.values()])
    v_pad = _pad_pow2(max(v_max, 1), floor=v_floor)
    prio = np.zeros((n_pad, v_pad))
    vec = np.zeros((n_pad, v_pad, d))
    valid = np.zeros((n_pad, v_pad), dtype=bool)
    flagged = np.zeros((n_pad, v_pad), dtype=bool)
    mp = np.zeros((n_pad, v_pad))
    grp = np.zeros((n_pad, v_pad), dtype=np.int32)
    cnt0 = np.zeros((n_pad, v_pad))
    src = np.full((n_pad, v_pad), -1, np.int64)
    off = np.zeros((n_pad, v_pad), np.int64)
    gid = np.zeros((n_pad, v_pad), np.int64)
    holds = np.zeros(n_pad, dtype=bool)
    counts = plan_preempted_counts(plan)

    # the kept nodes, as arrays
    keys = list(kept.keys)
    at, col, s = kept.node, kept.col, kept.src
    holds[kept.holds] = True
    prio[at, col] = kept.prio[s]
    vec[at, col] = kept.vec[s][:, :d]
    valid[at, col] = True
    flagged[at, col] = kept.flag[s]
    mp[at, col] = kept.mp[s]
    g = kept.gid[s]
    _, first, back = np.unique(at * max(len(keys), 1) + g,
                               return_index=True, return_inverse=True)
    grp[at, col] = col[first][back.reshape(-1)]
    cnt0[at, col] = np.array([counts.get(k, 0) for k in keys],
                             dtype=np.float64)[g] if keys else 0.0
    src[at, col] = s
    off[at, col] = kept.off
    gid[at, col] = g

    # the walked nodes, node by node
    refs: list = []
    kid = {k: i for i, k in enumerate(keys) if k is not None}
    for i, (cands, held) in walked.items():
        holds[i] = held
        if not cands:
            continue
        k = len(cands)
        prio[i, :k] = [c[0] for c in cands]
        vec[i, :k] = [c[2][:d] for c in cands]
        valid[i, :k] = True
        flagged[i, :k] = [c[3] for c in cands]
        mp[i, :k] = [c[5] for c in cands]
        firsts: Dict[tuple, int] = {}
        for v, c in enumerate(cands):
            grp[i, v] = firsts.setdefault(c[4], v)
            cnt0[i, v] = counts.get(c[4], 0)
            if c[4] not in kid:
                kid[c[4]] = len(keys)
                keys.append(c[4])
            gid[i, v] = kid[c[4]]
        at_ref = len(kept.ref) + len(refs)
        src[i, :k] = np.arange(at_ref, at_ref + k)
        refs.extend(c[1] for c in cands)
    walked_refs = np.empty(len(refs), dtype=object)
    walked_refs[:] = refs
    names = _ColumnNames(
        ctx.snapshot, src, off, gid, np.concatenate([kept.ref, walked_refs]),
        np.concatenate([kept.sub, np.full(len(refs), -1, np.int64)]), keys)
    return SystemVictims(v_pad, prio, vec, valid, flagged, mp, grp, cnt0,
                         holds, names, int(valid.sum()), int(kept.ok.sum()))


@dataclass
class TaskGroupTensors:
    """Everything kernels.solve_task_group needs for one task group."""

    ask: np.ndarray                 # (D,)
    feasible: np.ndarray            # (Np,) bool
    affinity_boost: np.ndarray      # (Np,)
    placed_tg: np.ndarray           # (Np,) int32
    placed_job: np.ndarray          # (Np,) int32
    # S (val_id (Np,) int32, val_ok (Np,) bool) pairs: the static's
    # interned rows themselves, never copied (their identity keys the
    # device-resident copy, solver.resident_rows)
    spread_rows: tuple
    spread_counts: np.ndarray       # (S, V) int32
    spread_desired: np.ndarray      # (S, V) float (NaN = no target)
    spread_has_targets: np.ndarray  # (S,) bool
    spread_weight: np.ndarray       # (S,)
    tg_count: float
    dh_job: bool
    dh_tg: bool
    spread_alg: bool
    # device/core count columns appended to the dense resource dims
    # (E = n device asks + 1 if reserved cores are requested)
    extra_cap: np.ndarray = None    # (Np, E)
    extra_used: np.ndarray = None   # (Np, E)
    extra_ask: np.ndarray = None    # (E,)
    dev_affinity: np.ndarray = None  # (Np,) device-affinity sub-score
    # per node, per device ask: the ids of the node's device groups that
    # serve it (the capacity columns' own match; None without an ask)
    dev_groups: list = None
    # distinct_property cap tables (reference propertyset.go); P
    # interned row pairs as spread_rows
    dp_rows: tuple = ()
    dp_counts: np.ndarray = None    # (P, Vd) int32
    dp_limit: np.ndarray = None     # (P,)
    # the SHARED cached mask instance when `feasible` is exactly the
    # static mask (no per-eval csi/ports adjustments): its identity keys
    # the device-resident copy for the bulk solve and the scan
    feas_base: np.ndarray = None

    @property
    def spread_val_id(self) -> np.ndarray:
        """(S, Np) int32, stacked from spread_rows (a copy: for readers
        that want the matrix, not the scan's staging)."""
        return _stack_rows(self.spread_rows, 0, len(self.feasible))

    @property
    def spread_val_ok(self) -> np.ndarray:
        return _stack_rows(self.spread_rows, 1, len(self.feasible))

    @property
    def dp_val_id(self) -> np.ndarray:
        return _stack_rows(self.dp_rows, 0, len(self.feasible))

    @property
    def dp_val_ok(self) -> np.ndarray:
        return _stack_rows(self.dp_rows, 1, len(self.feasible))


def _stack_rows(rows, which: int, n_pad: int) -> np.ndarray:
    if not rows:
        return np.zeros((0, n_pad), dtype=bool if which else np.int32)
    return np.stack([row[which] for row in rows])


def _affinity_vector(ctx: EvalContext, job: Job, tg: TaskGroup,
                     cluster: ClusterTensors) -> np.ndarray:
    """Precompute the node-affinity boost per node
    (reference rank.go:710 NodeAffinityIterator, sum(weight)/sum|weight|).
    Depends only on node attributes — cached on the ClusterStatic by
    affinity signature."""
    nodes, n_pad = cluster.nodes, cluster.n_pad
    affinities = (list(job.affinities) + list(tg.affinities)
                  + [a for t in tg.tasks for a in t.affinities])
    static = cluster.static
    if not affinities:
        if static is not None:
            # a stable zero instance so the device-resident cache can
            # key on identity
            hit = static.aff_cache.get(())
            if hit is None:
                hit = static.aff_cache[()] = np.zeros(n_pad)
            return hit
        return np.zeros(n_pad)
    sig = tuple((a.ltarget, a.operand, a.rtarget, a.weight)
                for a in affinities)
    if static is not None:
        hit = static.aff_cache.get(sig)
        if hit is not None:
            return hit
    total_weight = sum(abs(a.weight) for a in affinities) or 1.0
    out = np.zeros(n_pad)
    for i, node in enumerate(nodes):
        total = 0.0
        for aff in affinities:
            lval, lok = resolve_target(aff.ltarget, node)
            rval, rok = resolve_target(aff.rtarget, node)
            if check_constraint(aff.operand, lval, rval, lok, rok,
                                ctx.regex_cache, ctx.version_cache):
                total += aff.weight
        out[i] = total / total_weight
    if static is not None:
        static.aff_cache[sig] = out
    return out


def _interned_attr(ctx: EvalContext, cluster: ClusterTensors,
                   attribute: str):
    """-> (vocab, val_id (Np,), val_ok (Np,)) for one node attribute,
    cached on the ClusterStatic. The vocab keeps growing as off-pool
    nodes' values get interned by callers (append-only, so cached val_id
    arrays stay valid)."""
    static = cluster.static
    key = ("attr", attribute)
    if static is not None:
        hit = static.intern_cache.get(key)
        if hit is not None:
            return hit
    vocab: Dict[str, int] = {}
    val_id = np.zeros(cluster.n_pad, dtype=np.int32)
    val_ok = np.zeros(cluster.n_pad, dtype=bool)
    for i, node in enumerate(cluster.nodes):
        v, ok = resolve_target(attribute, node)
        if ok:
            vid = vocab.setdefault(v, len(vocab))
            val_id[i] = vid
            val_ok[i] = True
    out = (vocab, val_id, val_ok)
    if static is not None:
        static.intern_cache[key] = out
    return out


_intern_lock = __import__("threading").Lock()


def _intern(vocab: Dict[str, int], v: str) -> int:
    """Append-only interning safe under concurrent workers sharing a
    cached vocab (double-checked under a lock so two threads can never
    mint the same id for different values)."""
    vid = vocab.get(v)
    if vid is None:
        with _intern_lock:
            vid = vocab.get(v)
            if vid is None:
                vid = len(vocab)
                vocab[v] = vid
    return vid


def _spread_tensors(ctx: EvalContext, job: Job, tg: TaskGroup,
                    cluster: ClusterTensors):
    """Intern spread-attribute values and lower desired/existing counts
    (reference spread.go computeSpreadInfo + propertyset.go). The
    per-node interning rows come from the ClusterStatic cache and are
    handed on as they are; only the existing-alloc counts (O(job
    allocs)) are computed per eval."""
    spreads = combined_spreads(job, tg)
    s = len(spreads)
    if s == 0:
        return ((), np.zeros((0, 1), dtype=np.int32),
                np.full((0, 1), np.nan), np.zeros(0, dtype=bool),
                np.zeros(0))

    sum_weights = sum(abs(sp.weight) for sp in spreads) or 1.0
    existing = [a for a in ctx.snapshot.allocs_by_job(job.id, job.namespace)
                if not a.terminal_status() and a.task_group == tg.name]

    vocabs: List[Dict[str, int]] = []
    rows = []
    counts_list: List[Dict[int, int]] = []

    for si, sp in enumerate(spreads):
        vocab, vid_row, vok_row = _interned_attr(ctx, cluster, sp.attribute)
        rows.append((vid_row, vok_row))
        counts: Dict[int, int] = {}
        for a in existing:
            anode = ctx.snapshot.node_by_id(a.node_id)
            if anode is None:
                continue
            v, ok = resolve_target(sp.attribute, anode)
            if ok:
                vid = _intern(vocab, v)
                counts[vid] = counts.get(vid, 0) + 1
        vocabs.append(vocab)
        counts_list.append(counts)

    # snapshot the (shared, concurrently-growing) vocabs ONCE: every vid
    # this eval references was interned above, so a stable items() copy
    # taken here bounds v_pad and survives other workers' later inserts
    vocab_items = [list(v.items()) for v in vocabs]
    v_pad = _pad_pow2(max(max(len(v) for v in vocab_items), 1), floor=1)
    spread_counts = np.zeros((s, v_pad), dtype=np.int32)
    spread_desired = np.full((s, v_pad), np.nan)
    has_targets = np.zeros(s, dtype=bool)
    weights = np.zeros(s)

    for si, sp in enumerate(spreads):
        weights[si] = sp.weight / sum_weights
        for vid, c in counts_list[si].items():
            spread_counts[si, vid] = c
        if not sp.targets:
            continue
        has_targets[si] = True
        # desired-count semantics live in SpreadInfo (reference
        # spread.go:268 computeSpreadInfo) — reuse, don't re-derive
        desired = SpreadInfo(sp, tg.count).desired_counts
        implicit = desired.get(IMPLICIT_TARGET)
        for val, vid in vocab_items[si]:
            if val in desired:
                spread_desired[si, vid] = desired[val]
            elif implicit is not None:
                spread_desired[si, vid] = implicit
    return tuple(rows), spread_counts, spread_desired, has_targets, weights


def _device_core_tensors(ctx: EvalContext, tg: TaskGroup,
                         cluster: ClusterTensors):
    """Per-ask device capacity/usage columns + a reserved-cores column +
    the device-affinity sub-score vector + per node and ask the matched
    group ids. Capacity is constraint-filtered per ask (reference
    feasible.go:1259 DeviceChecker + device.go); usage comes from the
    store's device-usage rows plus plan deltas (the in-flight overlay's
    instances are added under the solve lock: fold_inflight_devices).
    Span `build.device_columns` (a group that asks a device or cores).

    Count-fit on the device is intentionally slightly optimistic when
    several asks share one group's instances or NUMA "require" constrains
    core identity: the post-solve host assignment catches those and falls
    back per request (same contract as exact port numbers)."""
    ask_res = ctx.tg_resources(tg)
    asks = ask_res.devices
    cores = int(ask_res.cores)
    e = len(asks) + (1 if cores else 0)
    if e == 0:
        n_pad = cluster.n_pad
        z = np.zeros((n_pad, 0))
        return z, z, np.zeros(0), np.zeros(n_pad), None
    with TRACER.span("build.device_columns", asks=len(asks),
                     cores=bool(cores)):
        return _device_core_columns(ctx, asks, cores, cluster)


def _device_core_columns(ctx: EvalContext, asks, cores: int,
                         cluster: ClusterTensors):
    from ..scheduler.devices import (accumulate_dev_usage,
                                     device_affinity_boost, groups_capacity,
                                     matching_groups)

    e = len(asks) + (1 if cores else 0)
    nodes = cluster.nodes
    n_pad = cluster.n_pad
    snap = ctx.snapshot
    used = np.zeros((n_pad, e))
    any_affinities = any(a.affinities for a in asks)

    # capacity columns + device-affinity boost depend only on node
    # hardware and the ask — cached on the ClusterStatic by ask signature
    static = cluster.static
    sig = (tuple((a.name, a.count,
                  tuple((c.ltarget, c.operand, c.rtarget)
                        for c in a.constraints),
                  tuple((f.ltarget, f.operand, f.rtarget, f.weight)
                        for f in a.affinities))
                 for a in asks), bool(cores))
    cached = static.dev_cache.get(sig) if static is not None else None
    if cached is not None:
        cap, dev_aff, match_lists = cached
    else:
        cap = np.zeros((n_pad, e))
        dev_aff = np.zeros(n_pad)
        # per (node, ask) matched group ids, reused by the usage fill
        match_lists = [[()] * len(asks) for _ in range(len(nodes))]
        for i, node in enumerate(nodes):
            for ei, ask in enumerate(asks):
                groups = matching_groups(node, ask, ctx.regex_cache,
                                         ctx.version_cache)
                cap[i, ei] = groups_capacity(groups)
                match_lists[i][ei] = tuple(g.id for g in groups)
            if cores:
                cap[i, -1] = node.resources.total_cores
            if any_affinities:
                dev_aff[i] = device_affinity_boost(
                    node, asks, ctx.regex_cache, ctx.version_cache)
        if static is not None:
            static.dev_cache[sig] = (cap, dev_aff, match_lists)

    plan = ctx.plan
    touched = set()
    if plan is not None:
        touched = (set(plan.node_update) | set(plan.node_preemptions)
                   | set(plan.node_allocation))
        for block in plan.alloc_blocks:
            if block.devices is not None:
                touched.update(block.live_node_counts()[0])
    for i, node in enumerate(nodes):
        if node.id in touched:
            row = {}
            for a in ctx.proposed_allocs(node.id):
                accumulate_dev_usage(row, a)
        else:
            row = snap.node_dev_usage(node.id)
        if not row:
            continue
        for ei in range(len(asks)):
            used[i, ei] = sum(row.get(gid, 0) for gid in match_lists[i][ei])
        if cores:
            used[i, -1] = row.get("cores", 0)
    extra_ask = np.array([float(a.count) for a in asks]
                         + ([float(cores)] if cores else []))
    return cap, used, extra_ask, dev_aff, match_lists if asks else None


def fold_inflight_devices(extra_used: np.ndarray, cluster: ClusterTensors,
                          dev_groups: list, held: list) -> None:
    """Add the device instances the in-flight overlay holds (`held`:
    [{node id: {device group id: instance ids}}], one map an entry,
    INFLIGHT.devices_held) to a device-asking group's count columns
    (`extra_used`, in place), each through the groups that serve its
    ask on that node (`dev_groups`, TaskGroupTensors.dev_groups):
    racing evaluations' instances, as their usage is folded into the
    dense dims. Called under the solve lock: one pass over the entries'
    nodes in the interpreter, one indexed add."""
    index = cluster.node_index
    rows, cols, adds = [], [], []
    for by_node in held:
        for node_id, groups in by_node.items():
            i = index.get(node_id)
            if i is None or i >= len(dev_groups):
                continue
            for ei, gids in enumerate(dev_groups[i]):
                n = 0
                for gid in gids:
                    ids = groups.get(gid)
                    if ids:
                        n += len(ids)
                if n:
                    rows.append(i)
                    cols.append(ei)
                    adds.append(n)
    if rows:
        np.add.at(extra_used, (rows, cols), adds)


def _distinct_property_tensors(ctx: EvalContext, job: Job, tg: TaskGroup,
                               cluster: ClusterTensors):
    """Interned distinct_property values + proposed counts + limits.
    Counts mirror the host mask's inputs (scheduler/rank.py
    _plan_aware_job_allocs -> feasible.distinct_property_mask): the job's
    live allocs as the in-progress plan would leave them."""
    from ..scheduler.feasible import distinct_property_constraints
    from ..scheduler.rank import _plan_aware_job_allocs

    constraints = distinct_property_constraints(job, tg)
    p = len(constraints)
    if p == 0:
        return (), np.zeros((0, 1), dtype=np.int32), np.zeros(0)

    live = [a for a in _plan_aware_job_allocs(ctx, job)
            if not a.terminal_status()]
    rows = []
    limits = np.zeros(p)
    counts_list = []
    vocabs = []
    for pi, c in enumerate(constraints):
        try:
            limits[pi] = int(c.rtarget) if c.rtarget else 1
        except ValueError:
            limits[pi] = 1
        vocab, vid_row, vok_row = _interned_attr(ctx, cluster, c.ltarget)
        rows.append((vid_row, vok_row))
        counts: Dict[int, int] = {}
        for a in live:
            anode = ctx.snapshot.node_by_id(a.node_id)
            if anode is None:
                continue
            v, ok = resolve_target(c.ltarget, anode)
            if ok and v in vocab:
                counts[vocab[v]] = counts.get(vocab[v], 0) + 1
        vocabs.append(vocab)
        counts_list.append(counts)
    v_pad = _pad_pow2(max(max(len(v) for v in vocabs), 1), floor=1)
    dp_counts = np.zeros((p, v_pad), dtype=np.int32)
    for pi, counts in enumerate(counts_list):
        for vid, cnt in counts.items():
            dp_counts[pi, vid] = cnt
    return tuple(rows), dp_counts, limits


def build_task_group_tensors(
    ctx: EvalContext,
    job: Job,
    tg: TaskGroup,
    cluster: ClusterTensors,
    *,
    algorithm: str = enums.SCHED_ALG_BINPACK,
) -> TaskGroupTensors:
    nodes = cluster.nodes
    n_pad = cluster.n_pad

    static = cluster.static
    feas_base = None
    if static is not None:
        sig = tg_mask_signature(job, tg)
        base = static.mask_cache.get(sig)
        if base is None:
            base = np.zeros(n_pad, dtype=bool)
            base[: len(nodes)] = feasible_mask_static(
                job, tg, nodes, ctx.regex_cache, ctx.version_cache)
            base.setflags(write=False)
            static.mask_cache[sig] = base
        if any(v.type == "csi" for v in tg.volumes.values()):
            feas = base.copy()
            feas[: len(nodes)] &= csi_volume_mask(
                tg, nodes, ctx.snapshot, job.namespace, ctx.plan)
        else:
            # the cached padded mask itself: stable identity keys the
            # device-resident copy (placer bulk path). Copied before any
            # per-eval mutation (reserved-ports AND below).
            feas = base
            feas_base = base
    else:
        feas = np.zeros(n_pad, dtype=bool)
        feas[: len(nodes)] = feasible_mask(
            job, tg, nodes, ctx.regex_cache, ctx.version_cache,
            snapshot=ctx.snapshot, plan=ctx.plan)
    placed_tg, placed_job = cluster.placement_counts(job, tg, ctx)
    (spread_rows, counts, desired,
     has_targets, weights) = _spread_tensors(ctx, job, tg, cluster)
    dh_job, dh_tg = distinct_hosts_flags(job, tg)

    # Reserved ports: conflict-free nodes only, and at most one alloc of
    # this group per node (the group's second alloc would collide with
    # the first's static ports) — which is exactly the dh_tg constraint
    # the kernel already enforces. Dynamic-port exhaustion is the R_PORTS
    # dimension of ask/available; exact numbers assigned post-solve.
    if ctx.tg_resources(tg).reserved_port_asks():
        feas = feas.copy()  # may be the shared read-only cached mask
        feas_base = None
        feas[: len(nodes)] &= reserved_ports_mask(tg, nodes, ctx.proposed_allocs)
        dh_tg = True

    extra_cap, extra_used, extra_ask, dev_aff, dev_groups = (
        _device_core_tensors(ctx, tg, cluster))
    dp_rows, dp_counts, dp_limit = _distinct_property_tensors(
        ctx, job, tg, cluster)

    return TaskGroupTensors(
        ask=ctx.tg_vec(tg),
        feasible=feas,
        affinity_boost=_affinity_vector(ctx, job, tg, cluster),
        placed_tg=placed_tg,
        placed_job=placed_job,
        spread_rows=spread_rows,
        spread_counts=counts,
        spread_desired=desired,
        spread_has_targets=has_targets,
        spread_weight=weights,
        tg_count=float(max(tg.count, 1)),
        dh_job=dh_job,
        dh_tg=dh_tg,
        spread_alg=(algorithm == enums.SCHED_ALG_SPREAD),
        extra_cap=extra_cap,
        extra_used=extra_used,
        extra_ask=extra_ask,
        dev_affinity=dev_aff,
        dev_groups=dev_groups,
        dp_rows=dp_rows,
        dp_counts=dp_counts,
        dp_limit=dp_limit,
        feas_base=feas_base,
    )
