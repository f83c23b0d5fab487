"""The replicated state store (reference nomad/state/state_store.go, 7.5k LoC).

Single serialized writer (the FSM apply path, reference nomad/fsm.go:228)
+ many concurrent snapshot readers. Every mutation commits at a new
monotonically-increasing raft-style index which doubles as the MVCC
generation.

Write protocol: `_begin()` allocates the next generation *privately*;
mutations land in version chains at that generation; `_commit()` then
publishes the index and wakes blocking readers. Readers can therefore
never observe a half-applied generation, and snapshot acquisition is
atomic with the writer's min-live computation (both go through the
tracker's lock), so pruning can never strand a just-taken snapshot.

Rows are immutable by convention (same contract as go-memdb in the
reference): mutators always insert fresh objects; `copy_for_update`-style
shallow copies are used when deriving new rows from old ones.
"""

from __future__ import annotations

import copy
import threading
import time
import weakref
from itertools import repeat
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

from ..structs import enums
from ..structs.alloc import BLOCK_SEP, AllocBlock, Allocation, device_pairs
from ..structs.resources import RESOURCE_DIMS
from ..structs.deployment import Deployment
from ..structs.evaluation import Evaluation
from ..structs.job import Job
from ..structs.node import Node
from ..analysis.ownership import GLOBAL as _OWN
from ..analysis.sanitizer import sanitized
from ..obs import TRACER
from .alloc_columns import AllocColumns
from .mvcc import ConsList, SnapshotTracker, VersionedTable, cons, cons_from_iter, cons_iter
from .watch import WatchTable


def _block_alloc_fallback(alloc_id: str, lookup) -> Optional[Allocation]:
    """Resolve a block-position alloc id ("<block uuid>#<pos>") to its
    virtual row via `lookup(block_id)` — the ONE copy of the id-format /
    visibility protocol, shared by snapshot reads (gen-bounded lookup)
    and the writer's latest-row resolution."""
    sep = alloc_id.rfind(BLOCK_SEP)
    if sep < 0:
        return None
    block = lookup(alloc_id[:sep])
    if block is None:
        return None
    try:
        p = int(alloc_id[sep + 1:])
    except ValueError:
        return None
    if p < 0 or p >= block.size or not block.visible(p):
        return None
    return block.alloc_at(p)


class BlockRef:
    """Secondary-index entry pointing into an AllocBlock: `row` is a
    node row within the block, or -1 for "all rows" (job/eval indexes).
    Rides in the same cons cells as alloc-id strings; resolution
    materializes lazily and lets a promoted real row (same id in the
    allocs table) override the block's virtual row."""

    __slots__ = ("block_id", "row")

    def __init__(self, block_id: str, row: int = -1):
        self.block_id = block_id
        self.row = row


class _RowIndex(dict):
    """node id -> row of the store's dense per-node columns, with the
    reverse list. One object for the lifetime of a row assignment: a
    restore rebuilds the columns under a new one, so holding it is
    holding the assignment a row number was read under."""

    __slots__ = ("ids",)

    def __init__(self):
        super().__init__()
        self.ids: List[str] = []


class NodeColumns:
    """Reader of the store's dense per-node columns at ONE committed
    generation, a snapshot's: usage (node_usage) and the capacity open
    to a new placement (available_vec() of a node that is ready and not
    draining, -inf in every dimension of any other), addressed by row
    number so that a plan's node ids are looked up once and a block's
    once in its life. Row -1 is a node the store never saw: the
    columns' last row, which no node is given, reads like a node gone.

    `read` takes no lock. A generation check before and after the
    gathers stands in for one (a commit keeps _write_lock through its
    listener pass, and a verify that queued there would wait out every
    round): every writer of the columns works between _begin, which
    moves _next_gen first, and _commit, which moves _index last
    (restore_store sets _next_gen before it rebuilds them), so while
    both read the snapshot's generation no transaction is open and none
    has committed since, and if _next_gen still reads it after the
    gathers none began meanwhile. Otherwise, or after a restore gave
    the rows out anew, the rows are read a node at a time from the MVCC
    tables at the same generation: either way one committed generation,
    never a transaction half applied."""

    __slots__ = ("_snap", "_store", "_rows")

    def __init__(self, snap: "StateSnapshot"):
        self._snap = snap
        self._store = snap._store
        self._rows: _RowIndex = snap._store._usage_rows

    @property
    def assignment(self) -> _RowIndex:
        """The row assignment the row numbers belong to (what a cache
        of them has to be keyed by)."""
        return self._rows

    def capacity(self) -> int:
        """Rows the columns hold now, the spare last one included."""
        return self._store._usage_mat.shape[0]

    def rows(self, node_ids: List[str]) -> np.ndarray:
        """Row number per node id; -1 for a node the store never saw."""
        return np.fromiter(map(self._rows.get, node_ids, repeat(-1)),
                           np.int64, len(node_ids))

    def read(self, rows: np.ndarray):
        """-> (used (M, D), avail (M, D)) of `rows`, the caller's to
        write on."""
        store, index = self._store, self._snap.index
        if (store._index == index and store._next_gen == index
                and store._usage_rows is self._rows):
            used = store._usage_mat[rows]
            avail = store._avail_mat[rows]
            if store._next_gen == index:
                return used, avail
        return self._read_per_node(rows)

    def _read_per_node(self, rows: np.ndarray):
        snap, ids = self._snap, self._rows.ids
        used = np.zeros((len(rows), RESOURCE_DIMS))
        avail = np.full((len(rows), RESOURCE_DIMS), -np.inf)
        for i, row in enumerate(rows.tolist()):
            node = snap.node_by_id(ids[row]) if row >= 0 else None
            if node is None:
                continue
            avail[i] = open_capacity(node)
            base = snap.node_usage(node.id)
            if base is not None:
                used[i] = base
        return used, avail


def open_capacity(node: Node):
    """What a new placement may use of the node: available_vec(), or
    -inf where none may land (the plan applier's gate: status ready and
    not draining; eligibility keeps the scheduler away, not the
    applier)."""
    if node.status == enums.NODE_STATUS_READY and not node.drain:
        return node.available_vec()
    return -np.inf


class StateSnapshot:
    """A point-in-time read-only view (reference state_store.go:224 Snapshot).

    Cheap to hold: just a generation number. Release explicitly (context
    manager / close) or let the finalizer do it.
    """

    def __init__(self, store: "StateStore", gen: int):
        # gen must already be acquired in the store's tracker
        self._store = store
        self.index = gen
        self._finalizer = weakref.finalize(self, store._tracker.release, gen)

    def close(self) -> None:
        self._finalizer()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --- nodes ---

    def node_by_id(self, node_id: str) -> Optional[Node]:
        return self._store._nodes.get(node_id, self.index)

    def nodes(self) -> Iterator[Node]:
        return (n for _, n in self._store._nodes.iterate(self.index))

    def ready_nodes_in_pool(self, datacenters: Iterable[str], node_pool: str) -> List[Node]:
        """Reference scheduler/util.go:50 readyNodesInDCsAndPool.

        Cached per (node-set version, dcs, pool) when this snapshot's
        node view matches the latest one — the common case for scheduler
        workers, which snapshot right before evaluating. The returned
        list is shared: callers must not mutate it. Its order is the
        CANONICAL node order the tensor caches key their per-node arrays
        to (tie-breaking among equal scores is a kernel-side permutation,
        not a host-side shuffle)."""
        dcs = list(datacenters)
        store = self._store
        key = (tuple(sorted(dcs)), node_pool)
        if self.index >= store.node_set_index:
            hit = store._ready_nodes_cache.get(key)
            if hit is not None and hit[0] == store.node_set_version:
                return hit[1]
            version = store.node_set_version
            out = CanonicalNodeList(
                n for n in self.nodes()
                if n.ready() and n.in_pool(dcs, node_pool))
            # only tag as canonical (and publish) if no node write raced
            # the scan — a stale list tagged with the current version
            # would poison the shared ClusterStatic caches
            if (store.node_set_version == version
                    and self.index >= store.node_set_index):
                out.canonical_version = version
                out.canonical_key = key
                store._ready_nodes_cache[key] = (version, out)
            return out
        return [n for n in self.nodes()
                if n.ready() and n.in_pool(dcs, node_pool)]

    # --- jobs ---

    def job_by_id(self, job_id: str, namespace: str = "default") -> Optional[Job]:
        return self._store._jobs.get((namespace, job_id), self.index)

    def jobs(self) -> Iterator[Job]:
        return (j for _, j in self._store._jobs.iterate(self.index))

    def job_version(self, job_id: str, version: int, namespace: str = "default") -> Optional[Job]:
        return self._store._job_versions.get((namespace, job_id, version), self.index)

    def job_versions(self, job_id: str, namespace: str = "default") -> List[Job]:
        """All retained versions, newest first (reference
        state_store JobVersionsByID). Keyed lookups from the current
        version downward — O(versions of THIS job), never a table scan."""
        current = self.job_by_id(job_id, namespace)
        if current is None:
            return []
        out = []
        for v in range(current.version, -1, -1):
            row = self.job_version(job_id, v, namespace)
            if row is not None:
                out.append(row)
        return out

    # --- evals ---

    def eval_by_id(self, eval_id: str) -> Optional[Evaluation]:
        return self._store._evals.get(eval_id, self.index)

    def evals_by_job(self, job_id: str, namespace: str = "default") -> List[Evaluation]:
        cell = self._store._evals_by_job.get((namespace, job_id), self.index)
        out, seen = [], set()
        for eid in cons_iter(cell):
            if eid in seen:
                continue
            seen.add(eid)
            ev = self.eval_by_id(eid)
            if ev is not None:
                out.append(ev)
        return out

    def evals(self) -> Iterator[Evaluation]:
        return (e for _, e in self._store._evals.iterate(self.index))

    # --- allocs ---

    def alloc_by_id(self, alloc_id: str) -> Optional[Allocation]:
        a = self._store._allocs.get(alloc_id, self.index)
        if a is not None:
            return a
        return _block_alloc_fallback(
            alloc_id, lambda bid: self._store._alloc_blocks.get(bid, self.index))

    def allocs(self) -> Iterator[Allocation]:
        yield from (a for _, a in self._store._allocs.iterate(self.index))
        for _, block in self._store._alloc_blocks.iterate(self.index):
            for a in block.iter_allocs():
                # promoted rows already came out of the allocs table
                if self._store._allocs.get(a.id, self.index) is None:
                    yield a

    def alloc_blocks(self) -> Iterator[AllocBlock]:
        return (b for _, b in self._store._alloc_blocks.iterate(self.index))

    def alloc_block_by_id(self, block_id: str) -> Optional[AllocBlock]:
        return self._store._alloc_blocks.get(block_id, self.index)

    def _ids_from_index(self, table: VersionedTable, key) -> Iterator[str]:
        cell = table.get(key, self.index)
        seen = set()
        for _id in cons_iter(cell):
            if type(_id) is BlockRef:
                yield _id
                continue
            if _id not in seen:
                seen.add(_id)
                yield _id

    def _resolve_block_ref(self, ref: BlockRef, out: List[Allocation]) -> None:
        block = self._store._alloc_blocks.get(ref.block_id, self.index)
        if block is None:
            return
        rows = (block.live_rows() if ref.row < 0
                else (ref.row,) if ref.row not in block.rejected_rows
                else ())
        allocs_tbl = self._store._allocs
        for m in rows:
            for a in block.allocs_for_row(m):
                promoted = allocs_tbl.get(a.id, self.index)
                out.append(promoted if promoted is not None else a)

    def _allocs_from_index(self, table: VersionedTable, key) -> List[Allocation]:
        out: List[Allocation] = []
        for aid in self._ids_from_index(table, key):
            if type(aid) is BlockRef:
                self._resolve_block_ref(aid, out)
                continue
            a = self._store._allocs.get(aid, self.index)
            if a is not None:
                out.append(a)
        return out

    def allocs_by_node(self, node_id: str) -> List[Allocation]:
        return self._allocs_from_index(self._store._allocs_by_node, node_id)

    def _held_by_node(self, node_id: str, skip, of_block, of_row):
        """What each of the node's allocations that is not terminal
        holds (what the plan applier's allocs_fit counts of the node's
        existing allocations), read from its index entries with no
        Allocation materialised: a block's node row through
        `of_block(block, row)` -> [(position, held)] (its column, empty
        for a block without one), a row, and a position promoted to one,
        through `of_row(alloc)`. Allocation ids in `skip` are left out
        (an overlay that replaces them). The one walk of ports_by_node
        and devices_by_node."""
        store, index = self._store, self.index
        allocs = store._allocs
        for entry in self._ids_from_index(store._allocs_by_node, node_id):
            if type(entry) is BlockRef:
                block = store._alloc_blocks.get(entry.block_id, index)
                if block is None or entry.row in block.rejected_rows:
                    continue
                prefix = f"{block.id}{BLOCK_SEP}"
                for p, held in of_block(block, entry.row):
                    aid = prefix + str(p)
                    if aid in skip:
                        continue
                    row = allocs.get(aid, index)
                    if row is None:
                        yield held
                    elif not row.terminal_status():
                        yield of_row(row)
                continue
            if entry in skip:
                continue
            a = allocs.get(entry, index)
            if a is not None and not a.terminal_status():
                yield of_row(a)

    def ports_by_node(self, node_id: str, skip=()) -> List[int]:
        """The port numbers the node's allocations hold (_held_by_node):
        a row's allocated_ports, a block's port column at its node
        row."""
        out: List[int] = []
        for values in self._held_by_node(
                node_id, skip, AllocBlock.ports_for_row,
                lambda a: [pt.value for pt in a.allocated_ports]):
            out.extend(values)
        return out

    def devices_by_node(self, node_id: str,
                        skip=()) -> List[Tuple[str, str]]:
        """The (device group id, instance id) pairs the node's
        allocations hold (_held_by_node): a row's allocated_devices, a
        block's device column at its node row. An instance held twice is
        listed twice."""
        out: List[Tuple[str, str]] = []
        for pairs in self._held_by_node(node_id, skip,
                                        AllocBlock.devices_for_row,
                                        device_pairs):
            out.extend(pairs)
        return out

    def live_mask(self, alloc_ids: List[str]) -> np.ndarray:
        """Whether each allocation is live (not terminal) at this
        snapshot, a block position read from its block without being
        materialised (a promoted one as its row)."""
        store, index = self._store, self.index
        allocs, blocks = store._allocs, store._alloc_blocks
        out = np.zeros(len(alloc_ids), dtype=bool)
        for i, aid in enumerate(alloc_ids):
            a = allocs.get(aid, index)
            if a is not None:
                out[i] = not a.terminal_status()
                continue
            sep = aid.rfind(BLOCK_SEP)
            if sep < 0:
                continue
            block = blocks.get(aid[:sep], index)
            try:
                p = int(aid[sep + 1:])
            except ValueError:
                continue
            out[i] = (block is not None and 0 <= p < block.size
                      and block.visible(p))
        return out

    def allocs_by_node_terminal(self, node_id: str, terminal: bool) -> List[Allocation]:
        return [a for a in self.allocs_by_node(node_id) if a.terminal_status() == terminal]

    def allocs_by_job(self, job_id: str, namespace: str = "default") -> List[Allocation]:
        return self._allocs_from_index(self._store._allocs_by_job, (namespace, job_id))

    def allocs_by_eval(self, eval_id: str) -> List[Allocation]:
        return self._allocs_from_index(self._store._allocs_by_eval, eval_id)

    # --- deployments ---

    def deployments(self) -> Iterator[Deployment]:
        return (d for _, d in self._store._deployments.iterate(self.index))

    # --- ACL + variables reads ---

    def acl_policy(self, name: str):
        return self._store._acl_policies.get(name, self.index)

    def acl_policies(self):
        return (p for _, p in self._store._acl_policies.iterate(self.index))

    def acl_token_by_accessor(self, accessor_id: str):
        return self._store._acl_tokens.get(accessor_id, self.index)

    def acl_token_by_secret(self, secret_id: str):
        accessor = self._store._acl_secret_idx.get(secret_id, self.index)
        if accessor is None:
            return None
        return self._store._acl_tokens.get(accessor, self.index)

    def acl_tokens(self):
        return (t for _, t in self._store._acl_tokens.iterate(self.index))

    def one_time_token(self, secret: str):
        return self._store._one_time_tokens.get(secret, self.index)

    def scheduler_configuration(self):
        """The replicated runtime scheduler config, or None when the
        operator never set one (boot-time config applies)."""
        return self._store._scheduler_config.get("config", self.index)

    def scaling_events(self, job_id: str, namespace: str = "default"):
        return list(self._store._scaling_events.get(
            (namespace, job_id), self.index) or ())

    def region(self, name: str):
        return self._store._regions.get(name, self.index)

    def regions(self):
        return (r for _, r in self._store._regions.iterate(self.index))

    def auth_method(self, name: str):
        return self._store._auth_methods.get(name, self.index)

    def auth_methods(self):
        return (m for _, m in self._store._auth_methods.iterate(self.index))

    def binding_rules(self, auth_method: str = ""):
        for _, r in self._store._binding_rules.iterate(self.index):
            if not auth_method or r.auth_method == auth_method:
                yield r

    def binding_rule(self, rule_id: str):
        return self._store._binding_rules.get(rule_id, self.index)

    def acl_role(self, name: str):
        return self._store._acl_roles.get(name, self.index)

    def acl_roles(self):
        return (r for _, r in self._store._acl_roles.iterate(self.index))

    def variable(self, path: str, namespace: str = "default"):
        return self._store._variables.get((namespace, path), self.index)

    def variables(self, namespace: str = "default", prefix: str = ""):
        for (ns, path), v in self._store._variables.iterate(self.index):
            if ns == namespace and path.startswith(prefix):
                yield v

    # --- derived usage rows (consumed by the tensor layer) ---

    def node_usage(self, node_id: str):
        """Summed allocated_vec of the node's non-terminal allocs, or
        None (maintained incrementally on every alloc write)."""
        return self._store._node_usage.get(node_id, self.index)

    def node_dev_usage(self, node_id: str) -> Optional[dict]:
        """{device_group_id: instances_used, "cores": n} or None."""
        return self._store._node_dev_usage.get(node_id, self.index)

    def node_columns(self) -> "NodeColumns":
        """The dense per-node columns as this snapshot's generation
        holds them (the plan applier's fit input)."""
        return NodeColumns(self)

    # --- namespaces ---

    def namespace(self, name: str):
        ns = self._store._namespaces.get(name, self.index)
        if ns is not None:
            return ns
        from ..structs.operator import DEFAULT_NAMESPACE, Namespace

        if name == DEFAULT_NAMESPACE:
            return Namespace(name=name, description="built-in")
        return None

    def namespaces(self):
        from ..structs.operator import DEFAULT_NAMESPACE, Namespace

        seen = set()
        for name, ns in self._store._namespaces.iterate(self.index):
            seen.add(name)
            yield ns
        if DEFAULT_NAMESPACE not in seen:
            yield Namespace(name=DEFAULT_NAMESPACE, description="built-in")

    # --- node pools ---

    def node_pool(self, name: str):
        """Built-in pools exist implicitly with no overrides
        (reference structs/node_pool.go built-in pools)."""
        pool = self._store._node_pools.get(name, self.index)
        if pool is not None:
            return pool
        from ..structs.operator import BUILTIN_NODE_POOLS, NodePool

        if name in BUILTIN_NODE_POOLS:
            return NodePool(name=name, description="built-in")
        return None

    def node_pools(self):
        from ..structs.operator import BUILTIN_NODE_POOLS, NodePool

        seen = set()
        for name, p in self._store._node_pools.iterate(self.index):
            seen.add(name)
            yield p
        for name in BUILTIN_NODE_POOLS:
            if name not in seen:
                yield NodePool(name=name, description="built-in")

    # --- volumes ---

    def volume_by_id(self, vol_id: str, namespace: str = "default"):
        return self._store._volumes.get((namespace, vol_id), self.index)

    def volumes(self, namespace: Optional[str] = None):
        for (ns, _vid), v in self._store._volumes.iterate(self.index):
            if namespace is None or ns == namespace:
                yield v

    def service_registrations(self, namespace: Optional[str] = None):
        """Every live registration (reference ServiceRegistrationListRPC)."""
        for _, reg in self._store._services.iterate(self.index):
            if namespace is None or reg.namespace == namespace:
                yield reg

    def service_by_name(self, name: str, namespace: str = "default"):
        out = []
        for rid in self._ids_from_index(self._store._services_by_name,
                                        (namespace, name)):
            reg = self._store._services.get(rid, self.index)
            if reg is not None:
                out.append(reg)
        return out

    def deployment_by_id(self, dep_id: str) -> Optional[Deployment]:
        return self._store._deployments.get(dep_id, self.index)

    def deployments_by_job(self, job_id: str, namespace: str = "default") -> List[Deployment]:
        out = []
        for did in self._ids_from_index(self._store._deployments_by_job, (namespace, job_id)):
            d = self._store._deployments.get(did, self.index)
            if d is not None:
                out.append(d)
        return out

    def latest_deployment_by_job(self, job_id: str, namespace: str = "default") -> Optional[Deployment]:
        best = None
        for d in self.deployments_by_job(job_id, namespace):
            if best is None or d.create_index > best.create_index:
                best = d
        return best


class CanonicalNodeList(list):
    """A ready-node list in CANONICAL order, tagged with the node-set
    version it was computed at — the tensor layer keys its shared
    per-node arrays (capacity, masks, interning) to it. Shared between
    callers: never mutate."""

    canonical_version = None
    canonical_key = None


def _qualname(fn) -> str:
    """A commit listener's qualified name (`WatchTable._on_commit`), the
    `fn` arg of its store.listener span."""
    return getattr(fn, "__qualname__", None) or type(fn).__name__


@sanitized
class StateStore:
    """MVCC tables + serialized write path (reference nomad/state/state_store.go).

    Commit listeners let derived caches (the tensorizer's usage arrays,
    the event broker) update incrementally without rescans.
    """

    def __init__(self):
        self._write_lock = threading.RLock()
        self._index = 0          # last *published* (committed) generation
        self._next_gen = 0       # last allocated generation (>= _index during a write)
        self._tracker = SnapshotTracker()
        self._cond = threading.Condition()
        # Wall-clock source for the ts-fallbacks in the mutators below.
        # A plain (non-replicated) store stamps local time; attaching a
        # raft FSM swaps in a guard that refuses the read (raft/fsm.py),
        # because a replica applying the shared log must never stamp
        # replica-local time — the proposer embeds ts in the command.
        self._clock = time.time

        self._nodes = VersionedTable("nodes")
        self._jobs = VersionedTable("jobs")                  # key (ns, job_id)
        self._job_versions = VersionedTable("job_versions")  # key (ns, job_id, version)
        self._evals = VersionedTable("evals")
        self._allocs = VersionedTable("allocs")
        # columnar bulk placements (structs/alloc.py AllocBlock), keyed by
        # block id; individual rows materialize lazily and promote into
        # _allocs on first write
        self._alloc_blocks = VersionedTable("alloc_blocks")
        self._deployments = VersionedTable("deployments")
        # secondary indexes: cons-lists of ids (append-only; compacted on GC)
        self._allocs_by_node = VersionedTable("allocs_by_node")
        self._allocs_by_job = VersionedTable("allocs_by_job")
        self._allocs_by_eval = VersionedTable("allocs_by_eval")
        self._evals_by_job = VersionedTable("evals_by_job")
        self._deployments_by_job = VersionedTable("deployments_by_job")
        # ACL + variables (reference schema.go acl_* and variables tables)
        self._acl_policies = VersionedTable("acl_policies")     # key name
        self._acl_tokens = VersionedTable("acl_tokens")         # key accessor id
        # one-time tokens (reference schema.go one_time_token): ott
        # secret -> {"accessor_id", "expires"} rows, single-exchange
        self._one_time_tokens = VersionedTable("one_time_tokens")
        # cluster-wide runtime scheduler configuration (reference
        # schema.go scheduler_config: a raft-replicated singleton)
        self._scheduler_config = VersionedTable("scheduler_config")
        self._acl_secret_idx = VersionedTable("acl_secret_idx")  # secret -> accessor
        self._acl_roles = VersionedTable("acl_roles")           # key name
        self._auth_methods = VersionedTable("acl_auth_methods")  # key name
        self._regions = VersionedTable("regions")               # key name
        # per-(ns, job) scaling event rings (reference scaling_event)
        self._scaling_events = VersionedTable("scaling_events")
        self._binding_rules = VersionedTable("acl_binding_rules")  # key id
        self._variables = VersionedTable("variables")           # key (ns, path)
        self._volumes = VersionedTable("volumes")               # key (ns, id)
        self._node_pools = VersionedTable("node_pools")         # key name
        self._namespaces = VersionedTable("namespaces")         # key name
        # builtin service catalog (reference schema.go services table):
        # registration rows keyed by id, plus (ns, service_name) and
        # alloc-id indexes (the latter feeds terminal-alloc reaping)
        self._services = VersionedTable("services")             # key id
        self._services_by_name = VersionedTable("services_by_name")
        self._services_by_alloc = VersionedTable("services_by_alloc")
        # derived: per-node summed allocated_vec of usage-counting allocs,
        # maintained on every alloc write so tensorization reads one row
        # per node instead of walking every alloc (the tensor-era form of
        # the O(allocs) proposed-usage rescan)
        self._node_usage = VersionedTable("node_usage")
        # derived: per-node device-instance + reserved-core usage counts
        # ({device_group_id: n, "cores": n}) for the device/core columns
        # the tensor layer appends; only allocs that carry devices/cores
        # ever touch it
        self._node_dev_usage = VersionedTable("node_dev_usage")

        # Node-set version: bumped (with the index it happened at) on any
        # node-table write. The tensor layer's canonical-node-set caches
        # key on it; a snapshot may only consume those caches when its
        # index has caught up to node_set_index (same node view).
        self.node_set_version = 0
        self.node_set_index = 0
        self._ready_nodes_cache: Dict[tuple, tuple] = {}
        # Dense LATEST-state usage matrix: one row per node, summed
        # allocated_vec of usage-counting allocs, maintained in lockstep
        # with the MVCC _node_usage rows. The TPU placer reads it with one
        # fancy-index gather instead of 10K dict lookups per eval; it sees
        # freshest-committed usage (not snapshot usage) by design — newer
        # usage only makes the optimistic solve MORE accurate, and the
        # serialized plan applier still owns correctness.
        self._usage_rows = _RowIndex()
        self._usage_mat = np.zeros((256, RESOURCE_DIMS))
        # One more dense column on the same row index, for the plan
        # applier's array fit check: the capacity open to a new
        # placement (open_capacity: `available_vec()` of the node's
        # latest row while it is ready and not draining, -inf
        # otherwise, so that "used + asked <= avail" is the gate and
        # the fit in one comparison). Derived like _usage_mat: never in
        # a dump or the log, rebuilt on restore. Both are written only
        # between _begin and _commit, which is what lets
        # NodeColumns.read gather them without the lock. The last row
        # is given to no node: row -1, a node the store never saw.
        self._avail_mat = np.full((256, RESOURCE_DIMS), -np.inf)
        # Per-node allocation columns on the same row index, for a system
        # evaluation's victim columns (state/alloc_columns.py): built by
        # the first evaluation that reads them (alloc_columns()), kept
        # from then on by the writers that move _usage_mat, derived like
        # it, rebuilt on restore, read without the lock by per-node
        # stamps. A store no system evaluation reads keeps none.
        self._alloc_cols: Optional[AllocColumns] = None

        self._all_tables = [
            self._nodes, self._jobs, self._job_versions, self._evals, self._allocs,
            self._alloc_blocks,
            self._deployments, self._allocs_by_node, self._allocs_by_job,
            self._allocs_by_eval, self._evals_by_job, self._deployments_by_job,
            self._acl_policies, self._acl_tokens, self._acl_secret_idx,
            self._one_time_tokens, self._scheduler_config,
            self._acl_roles, self._auth_methods, self._binding_rules,
            self._regions, self._scaling_events,
            self._variables, self._volumes, self._node_pools,
            self._namespaces, self._services, self._services_by_name,
            self._services_by_alloc,
            self._node_usage, self._node_dev_usage,
        ]
        self._listeners: List[Callable[[int, list], None]] = []
        # parked blocking queries (state/watch.py): first listener so
        # watchers wake before heavier derived-cache listeners run
        self.watches = WatchTable(self)

    # --- infrastructure ---

    @property
    def latest_index(self) -> int:
        return self._index

    def snapshot(self) -> StateSnapshot:
        gen = self._tracker.acquire_atomic(lambda: self._index)
        return StateSnapshot(self, gen)

    def snapshot_min_index(self, index: int, timeout: float = 5.0) -> StateSnapshot:
        """Block until the store has applied `index`, then snapshot
        (reference state_store.go:251 SnapshotMinIndex; used by workers at
        nomad/worker.go:591)."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._index < index:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"state store did not reach index {index} (at {self._index})")
                self._cond.wait(remaining)
        return self.snapshot()

    def add_commit_listener(self, fn: Callable[[int, list], None]) -> None:
        self._listeners.append(fn)

    def _begin(self) -> Tuple[int, int]:
        """Allocate the next generation (unpublished) and compute the
        prune floor. Must hold _write_lock."""
        self._next_gen += 1
        if _OWN.active:
            # nomadown: writes by this thread until _commit are the store
            # stamping its own rows, not post-insert aliasing
            _OWN.txn_begin()
        # Readers can only ever be at <= the published index, and
        # acquire_atomic serializes with this floor computation.
        live = self._tracker.min_live(self._index)
        return self._next_gen, live

    def _commit(self, gen: int, events: list, spans: bool = False) -> None:
        """Publish `gen` and run the commit listeners inline. `spans`
        (the plan-results path alone asks) opens one store.listener span
        a listener, named by its `fn`: every other writer's commit,
        heartbeats least of all, pays nothing for it."""
        if _OWN.active:
            _OWN.txn_commit(gen, events)
        with self._cond:
            self._index = gen
            self._cond.notify_all()
        if not spans:
            for fn in self._listeners:
                fn(gen, events)
            return
        for fn in self._listeners:
            with TRACER.span("store.listener", fn=_qualname(fn)):
                fn(gen, events)

    def compact(self) -> int:
        """Prune version chains and drop invisible tombstones across all
        tables (called from the GC core job). Returns rows dropped."""
        with self._write_lock:
            floor = self._tracker.min_live(self._index)
            return sum(t.sweep(floor) for t in self._all_tables)

    def dump(self) -> dict:
        """Whole-state serialization (operator snapshot save + FSM
        snapshots; reference helper/snapshot + fsm.go Snapshot)."""
        from .persist import dump_store
        return dump_store(self)

    def restore_dump(self, data: dict) -> int:
        """Replace contents from a dump (operator snapshot restore;
        replicates through raft as a regular FSM mutation)."""
        from .persist import restore_store
        restore_store(self, data)
        return self._index

    # --- node mutations (reference FSM ApplyNode*) ---

    def upsert_node(self, node: Node) -> int:
        with self._write_lock:
            gen, live = self._begin()
            prev = self._nodes.get_latest(node.id)
            if prev is not None:
                node.create_index = prev.create_index
                # preserve fields the fingerprint re-registration doesn't own
                if node.drain_strategy is None and prev.drain_strategy is not None:
                    node.drain_strategy = prev.drain_strategy
                    node.scheduling_eligibility = prev.scheduling_eligibility
            else:
                node.create_index = gen
            node.modify_index = gen
            node._avail_vec = None  # caller may have mutated resources
            if not node.computed_class:
                node.compute_class()
            self._nodes.put(node.id, node, gen, live)
            self._node_columns_put(node)  # a row exists for every node
            self._bump_node_set(gen)
            self._commit(gen, [("node-upsert", node)])
            return gen

    def upsert_nodes(self, nodes: List[Node]) -> int:
        """Batched node upsert: one generation, one commit, one event
        per node (the swarm registration path — per-node commits would
        be one raft round trip each at 100K nodes)."""
        with self._write_lock:
            gen, live = self._begin()
            events = []
            for node in nodes:
                prev = self._nodes.get_latest(node.id)
                if prev is not None:
                    node.create_index = prev.create_index
                    if (node.drain_strategy is None
                            and prev.drain_strategy is not None):
                        node.drain_strategy = prev.drain_strategy
                        node.scheduling_eligibility = prev.scheduling_eligibility
                else:
                    node.create_index = gen
                node.modify_index = gen
                node._avail_vec = None
                if not node.computed_class:
                    node.compute_class()
                self._nodes.put(node.id, node, gen, live)
                self._node_columns_put(node)
                events.append(("node-upsert", node))
            self._bump_node_set(gen)
            self._commit(gen, events)
            return gen

    def update_nodes_status(self, node_ids: List[str], status: str,
                            ts: float = None) -> int:
        """Batched status flip: one generation for a whole expiry or
        recovery batch. Unknown ids are skipped, not raised — under raft
        a node may be deleted between proposing the batch and applying
        it, and the FSM must apply identically on every replica."""
        ts = ts if ts is not None else self._clock()
        with self._write_lock:
            gen, live = self._begin()
            events = []
            for node_id in node_ids:
                node = self._nodes.get_latest(node_id)
                if node is None:
                    continue
                node = copy.copy(node)
                node.status = status
                node.status_updated_at = ts
                node.modify_index = gen
                self._nodes.put(node_id, node, gen, live)
                self._node_columns_put(node)
                events.append(("node-status", node))
            self._bump_node_set(gen)
            self._commit(gen, events)
            return gen

    def _update_node(self, node_id: str, event: str, mutate) -> int:
        with self._write_lock:
            node = self._nodes.get_latest(node_id)
            if node is None:
                raise KeyError(f"node {node_id} not found")
            gen, live = self._begin()
            node = copy.copy(node)
            mutate(node)
            node.modify_index = gen
            self._nodes.put(node_id, node, gen, live)
            self._node_columns_put(node)
            self._bump_node_set(gen)
            self._commit(gen, [(event, node)])
            return gen

    def update_node_status(self, node_id: str, status: str, ts: float = None) -> int:
        ts = ts if ts is not None else self._clock()

        def mut(n):
            n.status = status
            n.status_updated_at = ts
        return self._update_node(node_id, "node-status", mut)

    def update_node_eligibility(self, node_id: str, eligibility: str) -> int:
        def mut(n):
            n.scheduling_eligibility = eligibility
        return self._update_node(node_id, "node-eligibility", mut)

    def update_node_drain(self, node_id: str, drain_strategy, mark_eligible: bool = False) -> int:
        def mut(n):
            n.drain_strategy = drain_strategy
            if drain_strategy is not None:
                n.scheduling_eligibility = enums.NODE_SCHED_INELIGIBLE
            elif mark_eligible:
                n.scheduling_eligibility = enums.NODE_SCHED_ELIGIBLE
        return self._update_node(node_id, "node-drain", mut)

    def delete_node(self, node_id: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            node = self._nodes.get_latest(node_id)
            self._nodes.delete(node_id, gen, live)
            self._node_usage.delete(node_id, gen, live)
            self._node_dev_usage.delete(node_id, gen, live)
            row = self._usage_rows.get(node_id)
            if row is not None:
                self._usage_mat[row] = 0.0
                self._avail_mat[row] = -np.inf
            self._bump_node_set(gen)
            self._commit(gen, [("node-delete", node)])
            return gen

    # --- job mutations (reference FSM ApplyJobRegister/Deregister) ---

    def upsert_job(self, job: Job) -> int:
        with self._write_lock:
            self._require_namespace(job.namespace)
            gen, live = self._begin()
            key = (job.namespace, job.id)
            prev = self._jobs.get_latest(key)
            if prev is not None:
                job.create_index = prev.create_index
                job.version = prev.version + 1
            else:
                job.create_index = gen
                job.version = 0
                if job.status != enums.JOB_STATUS_DEAD:
                    job.status = enums.JOB_STATUS_PENDING
            job.modify_index = gen
            job.job_modify_index = gen
            # Store a snapshot row so a re-upserted caller object can't
            # rewrite version history in place.
            row = copy.copy(job)
            self._jobs.put(key, row, gen, live)
            self._job_versions.put((job.namespace, job.id, job.version), row, gen, live)
            self._commit(gen, [("job-upsert", row)])
            return gen

    def delete_job(self, job_id: str, namespace: str = "default", purge: bool = True) -> int:
        with self._write_lock:
            gen, live = self._begin()
            key = (namespace, job_id)
            job = self._jobs.get_latest(key)
            if purge:
                self._jobs.delete(key, gen, live)
                # a later job re-using the id must not inherit this
                # job's scaling history (reference DeleteJobTxn deletes
                # scaling events with the job)
                self._scaling_events.delete(key, gen, live)
            elif job is not None:
                job = copy.copy(job)
                job.stop = True
                job.modify_index = gen
                self._jobs.put(key, job, gen, live)
            self._commit(gen, [("job-delete", job)])
            return gen

    def update_job_status(self, job_id: str, status: str, namespace: str = "default") -> int:
        with self._write_lock:
            key = (namespace, job_id)
            job = self._jobs.get_latest(key)
            if job is None:
                raise KeyError(f"job {job_id} not found")
            gen, live = self._begin()
            job = copy.copy(job)
            job.status = status
            job.modify_index = gen
            self._jobs.put(key, job, gen, live)
            self._commit(gen, [("job-status", job)])
            return gen

    # --- eval mutations (reference FSM ApplyUpdateEval) ---

    def upsert_evals(self, evals: List[Evaluation], ts: float = None) -> int:
        with self._write_lock:
            gen, live = self._begin()
            ts = ts if ts is not None else self._clock()
            events = []
            for ev in evals:
                self._put_eval(ev, gen, live, ts)
                events.append(("eval-upsert", ev))
            self._commit(gen, events)
            return gen

    def _put_eval(self, ev: Evaluation, gen: int, live: int, ts: float = None) -> None:
        prev = self._evals.get_latest(ev.id)
        ev.create_index = prev.create_index if prev is not None else gen
        ev.modify_index = gen
        # ts flows from the proposer via the raft command so replicas stamp
        # identical times (replay-time stamping would fork GC decisions)
        ev.modify_time = ts if ts is not None else self._clock()
        if not ev.create_time:
            ev.create_time = ev.modify_time
        self._evals.put(ev.id, ev, gen, live)
        if prev is None:
            key = (ev.namespace, ev.job_id)
            cell = self._evals_by_job.get_latest(key)
            self._evals_by_job.put(key, cons(ev.id, cell), gen, live)

    def delete_evals(self, eval_ids: List[str]) -> int:
        with self._write_lock:
            gen, live = self._begin()
            dead = set(eval_ids)
            jobs_touched = set()
            for eid in eval_ids:
                ev = self._evals.get_latest(eid)
                if ev is not None:
                    jobs_touched.add((ev.namespace, ev.job_id))
                self._evals.delete(eid, gen, live)
            # compact the job index so dead eval ids don't accumulate
            # (sorted: set order is hash-randomized per process, and every
            # replica must rewrite the index chains identically)
            for key in sorted(jobs_touched):
                cell = self._evals_by_job.get_latest(key)
                ids = [i for i in cons_iter(cell) if i not in dead]
                if cell is not None and len(ids) != cell.length:
                    self._evals_by_job.put(key, cons_from_iter(reversed(ids)), gen, live)
            self._commit(gen, [("eval-delete", eval_ids)])
            return gen

    # --- alloc mutations ---

    def upsert_allocs(self, allocs: List[Allocation], ts: float = None) -> int:
        """Server-side alloc upsert (placements, desired-status changes)."""
        with self._write_lock:
            gen, live = self._begin()
            ts = ts if ts is not None else self._clock()
            events = []
            for alloc in allocs:
                self._put_alloc(alloc, gen, live, ts)
                events.append(("alloc-upsert", alloc))
            self._commit(gen, events)
            return gen

    def _bump_node_set(self, gen: int) -> None:
        """Must hold _write_lock. Invalidate canonical node-set caches."""
        self.node_set_version += 1
        self.node_set_index = gen
        self._ready_nodes_cache.clear()

    def _usage_row(self, node_id: str) -> int:
        """Must hold _write_lock when the row may need creating. The
        columns grow before the row is published in the index, so a
        lock-free reader that finds a row finds it in every column; the
        last row stays spare (NodeColumns)."""
        row = self._usage_rows.get(node_id)
        if row is None:
            row = len(self._usage_rows)
            size = self._usage_mat.shape[0]
            if row >= size - 1:
                for name, fill in (("_usage_mat", 0.0),
                                   ("_avail_mat", -np.inf)):
                    grown = np.full((size * 2, RESOURCE_DIMS), fill)
                    grown[: size - 1] = getattr(self, name)[: size - 1]
                    setattr(self, name, grown)
            self._usage_rows.ids.append(node_id)
            self._usage_rows[node_id] = row
        return row

    def _node_columns_put(self, node: Node) -> None:
        """Must hold _write_lock, inside a transaction: the capacity the
        node opens to a new placement, on its usage row."""
        row = self._usage_row(node.id)  # first: it may swap the columns
        self._avail_mat[row] = open_capacity(node)

    def usage_rows_for(self, node_ids: List[str]) -> np.ndarray:
        """Matrix row index per node id (for the tensor layer's one-gather
        usage read)."""
        rows = self._usage_rows
        try:
            return np.fromiter((rows[n] for n in node_ids), dtype=np.int64,
                               count=len(node_ids))
        except KeyError:
            with self._write_lock:
                return np.fromiter((self._usage_row(n) for n in node_ids),
                                   dtype=np.int64, count=len(node_ids))

    def _rebuild_usage_matrix(self) -> None:
        """Must hold _write_lock. Re-derive the dense columns from the
        MVCC node and usage rows (restore/install-snapshot path)."""
        self._usage_rows = _RowIndex()
        self._usage_mat = np.zeros((256, RESOURCE_DIMS))
        self._avail_mat = np.full((256, RESOURCE_DIMS), -np.inf)
        for _, node in self._nodes.iterate(self._next_gen):
            self._node_columns_put(node)
        for node_id, vec in self._node_usage.iterate(self._next_gen):
            if vec is not None:
                row = self._usage_row(node_id)
                self._usage_mat[row] = vec
        if self._alloc_cols is not None:
            self._alloc_cols = self._build_alloc_columns()

    def alloc_columns(self) -> AllocColumns:
        """The per-node allocation columns (state/alloc_columns.py):
        built from the latest tables on the first call, under the write
        lock, and kept by every write from then on."""
        cols = self._alloc_cols
        if cols is None:
            with self._write_lock:
                if self._alloc_cols is None:
                    self._alloc_cols = self._build_alloc_columns()
                cols = self._alloc_cols
        return cols

    def _build_alloc_columns(self) -> AllocColumns:
        """Must hold _write_lock. The columns of the latest tables, every
        node row stamped with the generation they are built at."""
        gen = self._next_gen
        cols = AllocColumns(self._usage_row, self._usage_rows, RESOURCE_DIMS,
                            gen=gen)
        cols.rebuild((a for _, a in self._allocs.iterate(gen)),
                     (b for _, b in self._alloc_blocks.iterate(gen)))
        return cols

    def _usage_add(self, node_id: str, delta, gen: int, live: int) -> None:
        cur = self._node_usage.get_latest(node_id)
        new = delta if cur is None else cur + delta
        self._node_usage.put(node_id, new, gen, live)
        row = self._usage_row(node_id)  # first: it may swap the columns
        self._usage_mat[row] += delta

    def _usage_apply(self, prev: Optional[Allocation], new: Optional[Allocation],
                     gen: int, live: int) -> None:
        """Fold one alloc transition into the per-node usage rows.

        Counting predicate is `not terminal_status()` — the scheduler's
        proposed-usage view (reference context.go:176 filters terminal
        allocs before the fit math ever sees them). The plan applier's
        stricter client-terminal-only accounting (funcs.go:150) stays in
        allocs_fit, which walks per-node allocs directly."""
        import numpy as np

        pc = prev is not None and not prev.terminal_status()
        nc = new is not None and not new.terminal_status()
        cols = self._alloc_cols
        if cols is not None:
            cols.transition(prev, new, pc, nc, gen,
                            self._alloc_blocks.get_latest)
        if (pc and nc and prev.node_id == new.node_id
                and np.array_equal(prev.allocated_vec, new.allocated_vec)):
            return  # annotation-only rewrite; no resource movement
        if pc:
            self._usage_add(prev.node_id, -prev.allocated_vec, gen, live)
            self._dev_usage_add(prev, -1, gen, live)
        if nc:
            self._usage_add(new.node_id, new.allocated_vec, gen, live)
            self._dev_usage_add(new, +1, gen, live)

    def _dev_usage_add(self, alloc: Allocation, sign: int, gen: int, live: int) -> None:
        if not alloc.allocated_devices and not alloc.allocated_cores:
            return
        from ..scheduler.devices import accumulate_dev_usage

        cur = self._node_dev_usage.get_latest(alloc.node_id)
        row = dict(cur) if cur else {}
        accumulate_dev_usage(row, alloc, sign)
        self._node_dev_usage.put(alloc.node_id, row, gen, live)

    def _dev_counts_add(self, node_id: str, counts: Dict[str, int],
                        gen: int, live: int) -> None:
        """Add {device group id: instances} to the node's device-usage
        row (a block's node row: AllocBlock.device_counts_for_row)."""
        if not counts:
            return
        cur = self._node_dev_usage.get_latest(node_id)
        row = dict(cur) if cur else {}
        for gid, n in counts.items():
            row[gid] = row.get(gid, 0) + n
        self._node_dev_usage.put(node_id, row, gen, live)

    _MISS = object()  # "caller did not look up prev" sentinel

    def _latest_alloc(self, alloc_id: str) -> Optional[Allocation]:
        """Latest row for an alloc id, falling back to its block's
        virtual row (first write to a block position "promotes" it: the
        new real row shadows the block position everywhere)."""
        a = self._allocs.get_latest(alloc_id)
        if a is not None:
            return a
        return _block_alloc_fallback(alloc_id, self._alloc_blocks.get_latest)

    def _put_alloc(self, alloc: Allocation, gen: int, live: int, ts: float = None,
                   prev=_MISS) -> None:
        alloc.modify_time = ts if ts is not None else self._clock()
        if prev is StateStore._MISS:
            prev = self._latest_alloc(alloc.id)
        if prev is not None:
            alloc.create_index = prev.create_index
            # client status is owned by the client update path; preserve it
            # on server-side rewrites unless explicitly set terminal
            if alloc.client_status == enums.ALLOC_CLIENT_PENDING and prev.client_status:
                alloc.client_status = prev.client_status
        else:
            alloc.create_index = gen
        alloc.modify_index = gen
        self._allocs.put(alloc.id, alloc, gen, live)
        self._usage_apply(prev, alloc, gen, live)
        if prev is None:
            cell = self._allocs_by_node.get_latest(alloc.node_id)
            self._allocs_by_node.put(alloc.node_id, cons(alloc.id, cell), gen, live)
            jkey = (alloc.namespace, alloc.job_id)
            jcell = self._allocs_by_job.get_latest(jkey)
            self._allocs_by_job.put(jkey, cons(alloc.id, jcell), gen, live)
            ecell = self._allocs_by_eval.get_latest(alloc.eval_id)
            self._allocs_by_eval.put(alloc.eval_id, cons(alloc.id, ecell), gen, live)

    def update_allocs_from_client(self, updates: List[Allocation], ts: float = None) -> int:
        """Client status sync (reference FSM ApplyAllocClientUpdate;
        client batches at client/client.go:2198)."""
        with self._write_lock:
            gen, live = self._begin()
            ts = ts if ts is not None else self._clock()
            events = []
            for upd in updates:
                existing = self._latest_alloc(upd.id)
                if existing is None:
                    continue
                merged = copy.copy(existing)
                merged.client_status = upd.client_status
                merged.client_description = upd.client_description
                merged.task_states = upd.task_states or merged.task_states
                merged.task_finished_at = upd.task_finished_at or merged.task_finished_at
                merged.deployment_status = upd.deployment_status or merged.deployment_status
                merged.modify_index = gen
                merged.modify_time = ts
                self._allocs.put(merged.id, merged, gen, live)
                self._usage_apply(existing, merged, gen, live)
                events.append(("alloc-client-update", merged))
                if merged.client_terminal():
                    self._reap_services_for_terminal(merged, gen, live,
                                                     events)
            self._commit(gen, events)
            return gen

    def update_alloc_desired_transitions(
            self, transitions: Dict[str, object], evals: List[Evaluation] = (),
            ts: float = None) -> int:
        """Reference FSM ApplyAllocUpdateDesiredTransition (used by drainer)."""
        with self._write_lock:
            gen, live = self._begin()
            events = []
            for alloc_id, transition in transitions.items():
                existing = self._latest_alloc(alloc_id)
                if existing is None:
                    continue
                merged = copy.copy(existing)
                merged.desired_transition = transition
                merged.modify_index = gen
                # desired_transition never flips should_count_for_usage
                # (that's client_terminal-only), so no usage row change;
                # a block position's first write gives it a row all the same
                self._allocs.put(alloc_id, merged, gen, live)
                if self._alloc_cols is not None:
                    self._alloc_cols.transition(
                        existing, merged, not existing.terminal_status(),
                        not merged.terminal_status(), gen,
                        self._alloc_blocks.get_latest)
                events.append(("alloc-transition", merged))
            for ev in evals:
                self._put_eval(ev, gen, live, ts)
                events.append(("eval-upsert", ev))
            self._commit(gen, events)
            return gen

    # --- the plan-apply mutation (reference state_store.go:369 UpsertPlanResults) ---

    def upsert_plan_results(
        self,
        result_allocs: List[Allocation],
        stopped_allocs: List[Allocation] = (),
        preempted_allocs: List[Allocation] = (),
        deployment: Optional[Deployment] = None,
        deployment_updates: List = (),
        evals: List[Evaluation] = (),
        alloc_blocks: List[AllocBlock] = (),
        job=None,
        ts: float = None,
        preempted_positions=None,
    ) -> int:
        return self.upsert_plan_results_batch([{
            "result_allocs": result_allocs, "stopped_allocs": stopped_allocs,
            "preempted_allocs": preempted_allocs, "deployment": deployment,
            "deployment_updates": deployment_updates, "evals": evals,
            "alloc_blocks": alloc_blocks, "job": job,
            "preempted_positions": preempted_positions}], ts=ts)

    def upsert_plan_results_batch(self, payloads: List[dict],
                                  ts: float = None) -> int:
        """Apply N plans' results in ONE transaction — one generation,
        one publish, one commit-listener pass — so the plan applier's
        group commit rides a single raft round instead of N. Each
        payload is a kwargs dict for upsert_plan_results (minus ts).
        Payloads apply in order: a later plan's update of an alloc an
        earlier payload inserted resolves exactly as it would across two
        back-to-back transactions, because get_latest sees same-gen
        puts."""
        # three phase spans split the transaction for the trace: the
        # wait for the writer lock, the apply, and the publish with its
        # inline listener pass. Children of plan.commit_round on the
        # applier's thread; roots beside raft.apply on the FSM's
        with TRACER.span("store.lock_wait"):
            self._write_lock.acquire()
        try:
            with TRACER.span("store.apply", payloads=len(payloads)) as sp:
                gen, live = self._begin()
                ts = ts if ts is not None else self._clock()
                events = []
                rows = blocks = 0
                for p in payloads:
                    self._apply_plan_payload(
                        p.get("result_allocs", ()),
                        p.get("stopped_allocs", ()),
                        p.get("preempted_allocs", ()),
                        p.get("deployment"),
                        p.get("deployment_updates", ()),
                        p.get("evals", ()),
                        p.get("alloc_blocks", ()),
                        gen, live, ts, events, job=p.get("job"),
                        preempted_positions=p.get("preempted_positions"))
                    rows += len(p.get("result_allocs", ())) + sum(
                        int(b.counts.sum()) for b in p.get("alloc_blocks", ()))
                    blocks += len(p.get("alloc_blocks", ()))
                sp.set(rows=rows, blocks=blocks)
            with TRACER.span("store.publish", events=len(events)):
                self._commit(gen, events, spans=True)
            return gen
        finally:
            self._write_lock.release()

    def _rehydrate_alloc_jobs(self, allocs, job) -> None:
        """Reverse of the plan applier's normalization: allocs ride the
        raft log without their embedded job (the plan's job rides once
        per payload). Re-attach — from the existing row when there is
        one (the exact version: stops and preemptions may carry an
        older job than the plan's), else the payload's job, else the
        job table. Deterministic across replicas: every input is FSM
        state or the replicated payload itself."""
        for a in allocs:
            if a.job is not None:
                continue
            prev = self._latest_alloc(a.id)
            if prev is not None and prev.job is not None:
                a.job = prev.job
            elif job is not None and getattr(job, "id", None) == a.job_id:
                a.job = job
            else:
                a.job = self._jobs.get_latest((a.namespace, a.job_id))

    def _supersede_slot_duplicates(self, new_allocs: List[Allocation],
                                   gen: int, live: int, ts: float,
                                   events: list) -> None:
        """A fresh placement whose slot (namespace, job_id, name)
        already holds a live alloc under a different id supersedes it:
        the older alloc is server-stopped inside the same transaction.

        Two plans CAN both commit for one slot across a failover — the
        dying leader's round lands in the log unanswered, the eval is
        re-run through the new leader before that suffix applies, and
        the re-plan places a fresh alloc id for a slot the first plan
        already filled. Serialized on one leader the applier would have
        stopped one of them; this does the same thing deterministically
        at apply time, on every replica. Canary placements are exempt
        (a canary intentionally runs beside the stable alloc of the
        same name), and so is an alloc in client state "unknown" (a
        disconnect replacement runs beside the original on purpose;
        the reconnect reconciliation picks the winner). Anything
        already terminal is skipped too — reschedules and migrations
        stop/fail their predecessor before or alongside the
        replacement, so they never trip this."""
        def slot(a: Allocation) -> tuple:
            # system/sysbatch place one same-named alloc PER NODE; the
            # slot identity there includes the node
            jtype = a.job.type if a.job is not None else ""
            node = (a.node_id if jtype in (enums.JOB_TYPE_SYSTEM,
                                           enums.JOB_TYPE_SYSBATCH)
                    else "")
            return (a.namespace, a.job_id, a.name, node)

        slots = {slot(a) for a in new_allocs if not a.canary}
        if not slots:
            return
        fresh_ids = {a.id for a in new_allocs}
        seen = set()
        # sorted, derived from the payload list: replicas must walk
        # jobs in one order (set iteration varies per process under
        # hash randomization) so the stop events land identically on
        # every FSM
        for jkey in sorted({(a.namespace, a.job_id)
                            for a in new_allocs if not a.canary}):
            for entry in cons_iter(self._allocs_by_job.get_latest(jkey)):
                if type(entry) is BlockRef:
                    block = self._alloc_blocks.get_latest(entry.block_id)
                    if block is None:
                        continue
                    cands = [a for m in block.live_rows()
                             for a in block.allocs_for_row(m)]
                else:
                    cands = [self._latest_alloc(entry)]
                for a in cands:
                    if (a is None or a.id in fresh_ids or a.id in seen
                            or a.canary):
                        continue
                    seen.add(a.id)
                    # block rows may shadow a promoted real row
                    cur = self._latest_alloc(a.id)
                    if (cur is None or cur.terminal_status()
                            or cur.client_status
                            == enums.ALLOC_CLIENT_UNKNOWN
                            or slot(cur) not in slots):
                        continue
                    stopped = cur.copy_for_update()
                    stopped.desired_status = enums.ALLOC_DESIRED_STOP
                    stopped.desired_description = (
                        "alloc superseded by a newer placement for the "
                        "same slot")
                    self._reap_services_for_terminal(stopped, gen, live,
                                                     events)
                    self._put_alloc(stopped, gen, live, ts)
                    events.append(("alloc-stop", stopped))

    def _apply_plan_payload(self, result_allocs, stopped_allocs,
                            preempted_allocs, deployment, deployment_updates,
                            evals, alloc_blocks, gen: int, live: int,
                            ts: float, events: list, job=None,
                            preempted_positions=None) -> None:
        """One plan's writes inside an open transaction. Must hold
        _write_lock; the caller owns _begin/_commit."""
        self._rehydrate_alloc_jobs(result_allocs, job)
        self._rehydrate_alloc_jobs(stopped_allocs, job)
        self._rehydrate_alloc_jobs(preempted_allocs, job)
        for alloc in stopped_allocs:
            self._reap_services_for_terminal(alloc, gen, live, events)
            self._put_alloc(alloc, gen, live, ts)
            events.append(("alloc-stop", alloc))
        for alloc in preempted_allocs:
            self._put_alloc(alloc, gen, live, ts)
            events.append(("alloc-preempt", alloc))
        if preempted_positions:
            events.extend(self._put_preempted_positions(
                preempted_positions, gen, live, ts))
        new_allocs: List[Allocation] = []
        for alloc in result_allocs:
            # ANY alloc without an existing row is a first insert and
            # must go through the bulk path, which records volume
            # claims — not just fresh placements (create_index == 0):
            # a re-upsert whose row was GC'd mid-flight still needs
            # its claims tracked. Block positions resolve via
            # _latest_alloc so a stop/annotation of a block alloc
            # promotes instead of double-indexing.
            prev = self._latest_alloc(alloc.id)
            if prev is None:
                new_allocs.append(alloc)
                continue
            self._put_alloc(alloc, gen, live, ts, prev=prev)
            events.append(("alloc-upsert", alloc))
        if new_allocs:
            self._supersede_slot_duplicates(new_allocs, gen, live, ts,
                                            events)
            self._put_new_allocs_bulk(new_allocs, gen, live, ts, events)
        for block in alloc_blocks:
            self._put_alloc_block(block, gen, live, ts, events)
        if deployment is not None:
            self._put_deployment(deployment, gen, live)
            events.append(("deployment-upsert", deployment))
        for du in deployment_updates:
            dep = self._deployments.get_latest(du.deployment_id)
            if dep is not None:
                dep = copy.copy(dep)
                dep.status = du.status
                dep.status_description = du.status_description
                dep.modify_index = gen
                self._deployments.put(dep.id, dep, gen, live)
                events.append(("deployment-update", dep))
        for ev in evals:
            self._put_eval(ev, gen, live, ts)
            events.append(("eval-upsert", ev))

    def _put_preempted_positions(self, taken, gen: int, live: int,
                                 ts: float) -> list:
        """A plan's column evictions (structs/plan.py PreemptedPositions):
        each victim that is still live becomes its evicted row, a block
        position promoted as any first write to one promotes it, with
        the id of the placement it made room for. The one place a
        victim is materialised. -> the events of the evictions."""
        events = []
        for aid, by in zip(taken.alloc_ids, taken.by):
            prev = self._latest_alloc(aid)
            if prev is None or prev.terminal_status():
                continue
            evicted = prev.copy_for_update()
            evicted.desired_status = enums.ALLOC_DESIRED_EVICT
            evicted.desired_description = f"Preempted by alloc ID {by}"
            evicted.preempted_by_allocation = by
            self._put_alloc(evicted, gen, live, ts, prev=prev)
            events.append(("alloc-preempt", evicted))
        return events

    def _put_new_allocs_bulk(self, allocs: List[Allocation], gen: int,
                             live: int, ts: float, events: list) -> None:
        """First-insert fast path for plan placements (the 2M-alloc
        shape): per-node usage deltas accumulate before touching the
        MVCC rows, and each secondary index key gets ONE put with all
        its new ids consed on — instead of five table round-trips per
        allocation. Semantically identical to _put_alloc for rows that
        don't exist yet (the caller checked)."""
        by_node: Dict[str, list] = {}
        by_job: Dict[tuple, list] = {}
        by_eval: Dict[str, list] = {}
        usage: Dict[str, object] = {}
        vol_memo: Dict[tuple, bool] = {}
        for a in allocs:
            a.modify_time = ts
            a.create_index = gen
            a.modify_index = gen
            self._allocs.put(a.id, a, gen, live)
            by_node.setdefault(a.node_id, []).append(a.id)
            by_job.setdefault((a.namespace, a.job_id), []).append(a.id)
            by_eval.setdefault(a.eval_id, []).append(a.id)
            if not a.terminal_status():
                # count per (node, vec identity): bulk placements share
                # one allocated_vec object per task group, so the numpy
                # adds collapse to one multiply per node
                ukey = (a.node_id, id(a.allocated_vec))
                e = usage.get(ukey)
                if e is None:
                    usage[ukey] = [a.allocated_vec, 1]
                else:
                    e[1] += 1
                if a.allocated_devices or a.allocated_cores:
                    self._dev_usage_add(a, +1, gen, live)
            key = (a.namespace, a.job_id, a.task_group)
            has_vols = vol_memo.get(key)
            if has_vols is None:
                tg = a.job.lookup_task_group(a.task_group) if a.job else None
                has_vols = vol_memo[key] = bool(tg is not None and tg.volumes)
            if has_vols:
                self._claim_volumes_for(a, gen, live, events)
            events.append(("alloc-upsert", a))
        for (node_id, _), (vec, count) in usage.items():
            self._usage_add(node_id, vec if count == 1 else vec * count,
                            gen, live)
        if self._alloc_cols is not None:
            self._alloc_cols.rows_added(allocs, gen)
        for table, groups in ((self._allocs_by_node, by_node),
                              (self._allocs_by_job, by_job),
                              (self._allocs_by_eval, by_eval)):
            for key, ids in groups.items():
                # one chunk cell per key per transaction (cons_iter
                # flattens tuple heads)
                cell = cons(tuple(ids), table.get_latest(key))
                table.put(key, cell, gen, live)

    def _put_alloc_block(self, block: AllocBlock, gen: int, live: int,
                         ts: float, events: list) -> None:
        """Insert one columnar placement batch: O(touched nodes) host
        work for K allocations — one block row, one BlockRef cons per
        touched node, one vectorized usage add. This is the 2M-alloc
        answer to _put_new_allocs_bulk's per-alloc loop. A block carries
        no cores or volumes by construction (the placer's gate); the
        ports and device instances of a group that asks them ride in its
        columns (AllocBlock.ports, AllocBlock.devices), which the plan
        applier verified, and the instances count in each node's
        device-usage row as a row's do."""
        block.modify_time = ts
        block.create_index = gen
        block.modify_index = gen
        self._alloc_blocks.put(block.id, block, gen, live)
        vec = block.allocated_vec
        for m in block.live_rows():
            nid = block.node_ids[m]
            c = int(block.counts[m])
            cell = self._allocs_by_node.get_latest(nid)
            self._allocs_by_node.put(nid, cons(BlockRef(block.id, m), cell),
                                     gen, live)
            self._usage_add(nid, vec * c if c != 1 else vec, gen, live)
            if block.devices is not None:
                self._dev_counts_add(nid, block.device_counts_for_row(m),
                                     gen, live)
        if self._alloc_cols is not None:
            self._alloc_cols.block_added(block, gen)
        jkey = (block.namespace, block.job_id)
        jcell = self._allocs_by_job.get_latest(jkey)
        self._allocs_by_job.put(jkey, cons(BlockRef(block.id), jcell),
                                gen, live)
        ecell = self._allocs_by_eval.get_latest(block.eval_id)
        self._allocs_by_eval.put(block.eval_id, cons(BlockRef(block.id), ecell),
                                 gen, live)
        events.append(("alloc-block-upsert", block))

    # --- deployments ---

    def _put_deployment(self, dep: Deployment, gen: int, live: int) -> None:
        prev = self._deployments.get_latest(dep.id)
        dep.create_index = prev.create_index if prev is not None else gen
        dep.modify_index = gen
        self._deployments.put(dep.id, dep, gen, live)
        if prev is None:
            key = (dep.namespace, dep.job_id)
            cell = self._deployments_by_job.get_latest(key)
            self._deployments_by_job.put(key, cons(dep.id, cell), gen, live)

    def upsert_deployment(self, dep: Deployment) -> int:
        with self._write_lock:
            gen, live = self._begin()
            self._put_deployment(dep, gen, live)
            self._commit(gen, [("deployment-upsert", dep)])
            return gen

    def delete_deployment(self, dep_id: str) -> int:
        """GC a terminal deployment (reference core_sched.go deploymentGC)."""
        with self._write_lock:
            gen, live = self._begin()
            dep = self._deployments.get_latest(dep_id)
            self._deployments.delete(dep_id, gen, live)
            self._commit(gen, [("deployment-delete", dep)])
            return gen

    def update_deployment_status(self, dep_id: str, status: str, description: str = "") -> int:
        with self._write_lock:
            dep = self._deployments.get_latest(dep_id)
            if dep is None:
                raise KeyError(f"deployment {dep_id} not found")
            gen, live = self._begin()
            dep = copy.copy(dep)
            dep.status = status
            if description:
                dep.status_description = description
            dep.modify_index = gen
            self._deployments.put(dep_id, dep, gen, live)
            self._commit(gen, [("deployment-update", dep)])
            return gen

    # --- volumes (reference state_store_csi + volumewatcher semantics) ---

    def upsert_volume(self, vol) -> int:
        with self._write_lock:
            self._require_namespace(vol.namespace)
            gen, live = self._begin()
            key = (vol.namespace, vol.id)
            prev = self._volumes.get_latest(key)
            if prev is not None:
                vol.create_index = prev.create_index
                # claims are store-owned state: a re-register must not wipe
                # live claims (reference CSIVolumeRegister merges)
                if not vol.claims and prev.claims:
                    vol.claims = dict(prev.claims)
            else:
                vol.create_index = gen
            vol.modify_index = gen
            self._volumes.put(key, vol, gen, live)
            self._commit(gen, [("volume-upsert", vol)])
            return gen

    def delete_volume(self, vol_id: str, namespace: str = "default",
                      force: bool = False) -> int:
        with self._write_lock:
            key = (namespace, vol_id)
            vol = self._volumes.get_latest(key)
            if vol is not None and vol.claims and not force:
                raise ValueError(
                    f"volume {vol_id} has {len(vol.claims)} live claims")
            gen, live = self._begin()
            self._volumes.delete(key, gen, live)
            self._commit(gen, [("volume-delete", vol)])
            return gen

    # --- service registrations (reference state_store_service_registration.go) ---

    def upsert_service_registrations(self, regs) -> int:
        with self._write_lock:
            gen, live = self._begin()
            events = []
            for reg in regs:
                prev = self._services.get_latest(reg.id)
                reg.create_index = prev.create_index if prev is not None else gen
                reg.modify_index = gen
                self._services.put(reg.id, reg, gen, live)
                if prev is None:
                    key = (reg.namespace, reg.service_name)
                    cell = self._services_by_name.get_latest(key)
                    self._services_by_name.put(key, cons(reg.id, cell),
                                               gen, live)
                    acell = self._services_by_alloc.get_latest(reg.alloc_id)
                    self._services_by_alloc.put(
                        reg.alloc_id, cons(reg.id, acell), gen, live)
                events.append(("service-register", reg))
            self._commit(gen, events)
            return gen

    def _delete_service_regs(self, ids, gen: int, live: int, events: list) -> None:
        for rid in ids:
            reg = self._services.get_latest(rid)
            if reg is None:
                continue
            self._services.delete(rid, gen, live)
            key = (reg.namespace, reg.service_name)
            cell = self._services_by_name.get_latest(key)
            left = [i for i in cons_iter(cell) if i != rid]
            self._services_by_name.put(
                key, cons_from_iter(reversed(left)), gen, live)
            acell = self._services_by_alloc.get_latest(reg.alloc_id)
            aleft = [i for i in cons_iter(acell) if i != rid]
            self._services_by_alloc.put(
                reg.alloc_id, cons_from_iter(reversed(aleft)) if aleft else None,
                gen, live)
            events.append(("service-deregister", reg))

    def _reap_services_for_terminal(self, alloc, gen: int, live: int,
                                    events: list) -> None:
        """A terminal alloc's registrations must not outlive it: the
        graceful client deregister never happens for crashed/lost nodes
        (reference: server-side deletion when the alloc goes terminal)."""
        cell = self._services_by_alloc.get_latest(alloc.id)
        if cell is None:
            return
        ids = list(cons_iter(cell))
        if ids:
            self._delete_service_regs(ids, gen, live, events)

    def delete_service_registrations(self, ids) -> int:
        with self._write_lock:
            gen, live = self._begin()
            events = []
            self._delete_service_regs(list(ids), gen, live, events)
            self._commit(gen, events)
            return gen

    def delete_services_by_alloc(self, alloc_id: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            cell = self._services_by_alloc.get_latest(alloc_id)
            ids = list(cons_iter(cell)) if cell is not None else []
            events = []
            if ids:
                self._delete_service_regs(ids, gen, live, events)
            self._commit(gen, events)
            return gen

    def _claim_volumes_for(self, alloc: Allocation, gen: int, live: int,
                           events: list) -> None:
        """Record this placement's csi-volume claims (called inside the
        plan-apply transaction; the applier pre-verified claimability).
        Readers claim too — the watcher tracks every attachment."""
        job = alloc.job
        if job is None:
            return
        tg = job.lookup_task_group(alloc.task_group)
        if tg is None or not tg.volumes:
            return
        from ..structs.volumes import VolumeClaim

        for req in tg.volumes.values():
            if req.type != "csi":
                continue
            key = (alloc.namespace, req.source)
            vol = self._volumes.get_latest(key)
            if vol is None:
                continue
            vol = copy.copy(vol)
            vol.claims = dict(vol.claims)
            vol.claims[alloc.id] = VolumeClaim(
                alloc_id=alloc.id, node_id=alloc.node_id,
                read_only=req.read_only)
            vol.modify_index = gen
            self._volumes.put(key, vol, gen, live)
            events.append(("volume-claim", vol))

    def reap_volume_claims(self) -> int:
        """Release claims whose allocs are terminal or gone (the volume
        watcher's reaping pass, reference nomad/volumewatcher/). Returns
        claims released."""
        with self._write_lock:
            changes = []
            for key, vol in list(self._volumes.iterate(self._index)):
                dead = [aid for aid in vol.claims
                        if (a := self._allocs.get_latest(aid)) is None
                        or a.terminal_status()]
                if dead:
                    changes.append((key, vol, dead))
            if not changes:
                return 0  # no generation churn on idle reaping passes
            gen, live = self._begin()
            events = []
            released = 0
            for key, vol, dead in changes:
                vol = copy.copy(vol)
                vol.claims = {k: v for k, v in vol.claims.items()
                              if k not in dead}
                vol.modify_index = gen
                self._volumes.put(key, vol, gen, live)
                events.append(("volume-claim-release", vol))
                released += len(dead)
            self._commit(gen, events)
            return released

    # --- namespaces (reference state_store namespaces table) ---

    def _require_namespace(self, name: str) -> None:
        """Authoritative existence check, called INSIDE mutations under
        _write_lock — the server-layer check is a fast-fail courtesy, but
        only this one closes the check-then-act window against a
        concurrent delete_namespace."""
        from ..structs.operator import DEFAULT_NAMESPACE

        if name == DEFAULT_NAMESPACE:
            return
        if self._namespaces.get_latest(name) is None:
            raise ValueError(f"namespace {name!r} does not exist")

    def upsert_namespace(self, ns) -> int:
        with self._write_lock:
            gen, live = self._begin()
            prev = self._namespaces.get_latest(ns.name)
            ns.create_index = prev.create_index if prev is not None else gen
            ns.modify_index = gen
            self._namespaces.put(ns.name, ns, gen, live)
            self._commit(gen, [("namespace-upsert", ns)])
            return gen

    def delete_namespace(self, name: str) -> int:
        from ..structs.operator import DEFAULT_NAMESPACE

        if name == DEFAULT_NAMESPACE:
            raise ValueError("cannot delete the default namespace")
        with self._write_lock:
            if self._namespaces.get_latest(name) is None:
                raise KeyError(f"namespace {name!r} does not exist")
            # non-empty namespaces must not vanish under their objects
            # (stopped jobs awaiting GC don't count)
            for (jns, _), j in self._jobs.iterate(self._index):
                if jns == name and not j.stopped():
                    raise ValueError(f"namespace {name!r} has jobs")
            for (vns, _), _v in self._volumes.iterate(self._index):
                if vns == name:
                    raise ValueError(f"namespace {name!r} has volumes")
            for (wns, _), _w in self._variables.iterate(self._index):
                if wns == name:
                    raise ValueError(f"namespace {name!r} has variables")
            gen, live = self._begin()
            ns = self._namespaces.get_latest(name)
            self._namespaces.delete(name, gen, live)
            self._commit(gen, [("namespace-delete", ns)])
            return gen

    # --- node pools (reference state_store_node_pools) ---

    def upsert_node_pool(self, pool) -> int:
        from ..structs.operator import BUILTIN_NODE_POOLS

        if pool.name in BUILTIN_NODE_POOLS:
            # enforced here as well as at the endpoint so the FSM apply
            # path can't rewrite the implicit pools either
            raise ValueError(f"cannot modify built-in node pool {pool.name!r}")
        with self._write_lock:
            gen, live = self._begin()
            prev = self._node_pools.get_latest(pool.name)
            pool.create_index = prev.create_index if prev is not None else gen
            pool.modify_index = gen
            self._node_pools.put(pool.name, pool, gen, live)
            self._commit(gen, [("node-pool-upsert", pool)])
            return gen

    def delete_node_pool(self, name: str) -> int:
        from ..structs.operator import BUILTIN_NODE_POOLS

        if name in BUILTIN_NODE_POOLS:
            raise ValueError(f"cannot delete built-in node pool {name!r}")
        with self._write_lock:
            # a pool with member nodes or jobs must not vanish under them
            for _, n in self._nodes.iterate(self._index):
                if n.node_pool == name:
                    raise ValueError(f"node pool {name!r} has nodes")
            for _, j in self._jobs.iterate(self._index):
                if j.node_pool == name and not j.stopped():
                    raise ValueError(f"node pool {name!r} has jobs")
            gen, live = self._begin()
            pool = self._node_pools.get_latest(name)
            self._node_pools.delete(name, gen, live)
            self._commit(gen, [("node-pool-delete", pool)])
            return gen

    # --- ACL (reference nomad/state/state_store acl tables) ---

    def upsert_acl_policy(self, policy) -> int:
        with self._write_lock:
            gen, live = self._begin()
            policy.modify_index = gen
            self._acl_policies.put(policy.name, policy, gen, live)
            self._commit(gen, [("acl-policy-upsert", policy)])
            return gen

    def delete_acl_policy(self, name: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            pol = self._acl_policies.get_latest(name)
            self._acl_policies.delete(name, gen, live)
            self._commit(gen, [("acl-policy-delete", pol)])
            return gen

    def upsert_acl_role(self, role) -> int:
        with self._write_lock:
            gen, live = self._begin()
            prev = self._acl_roles.get_latest(role.name)
            role.create_index = prev.create_index if prev is not None else gen
            role.modify_index = gen
            self._acl_roles.put(role.name, role, gen, live)
            self._commit(gen, [("acl-role-upsert", role)])
            return gen

    def delete_acl_role(self, name: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            role = self._acl_roles.get_latest(name)
            self._acl_roles.delete(name, gen, live)
            self._commit(gen, [("acl-role-delete", role)])
            return gen

    def append_scaling_event(self, job_id: str, namespace: str,
                             event: dict, keep: int = 20) -> int:
        with self._write_lock:
            gen, live = self._begin()
            key = (namespace, job_id)
            events = list(self._scaling_events.get_latest(key) or ())
            events.append(dict(event))
            self._scaling_events.put(key, tuple(events[-keep:]), gen, live)
            self._commit(gen, [("scaling-event", event)])
            return gen

    def upsert_region(self, region) -> int:
        with self._write_lock:
            gen, live = self._begin()
            prev = self._regions.get_latest(region.name)
            region.create_index = prev.create_index if prev is not None else gen
            region.modify_index = gen
            self._regions.put(region.name, region, gen, live)
            self._commit(gen, [("region-upsert", region)])
            return gen

    def delete_region(self, name: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            r = self._regions.get_latest(name)
            self._regions.delete(name, gen, live)
            self._commit(gen, [("region-delete", r)])
            return gen

    def upsert_auth_method(self, method) -> int:
        with self._write_lock:
            gen, live = self._begin()
            prev = self._auth_methods.get_latest(method.name)
            method.create_index = prev.create_index if prev is not None else gen
            method.modify_index = gen
            self._auth_methods.put(method.name, method, gen, live)
            self._commit(gen, [("auth-method-upsert", method)])
            return gen

    def delete_auth_method(self, name: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            m = self._auth_methods.get_latest(name)
            self._auth_methods.delete(name, gen, live)
            # rules of a deleted method are dead weight: drop them
            for rid, rule in list(self._binding_rules.iterate(gen)):
                if rule.auth_method == name:
                    self._binding_rules.delete(rid, gen, live)
            self._commit(gen, [("auth-method-delete", m)])
            return gen

    def upsert_binding_rule(self, rule) -> int:
        with self._write_lock:
            gen, live = self._begin()
            prev = self._binding_rules.get_latest(rule.id)
            rule.create_index = prev.create_index if prev is not None else gen
            rule.modify_index = gen
            self._binding_rules.put(rule.id, rule, gen, live)
            self._commit(gen, [("binding-rule-upsert", rule)])
            return gen

    def delete_binding_rule(self, rule_id: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            r = self._binding_rules.get_latest(rule_id)
            self._binding_rules.delete(rule_id, gen, live)
            self._commit(gen, [("binding-rule-delete", r)])
            return gen

    def upsert_acl_token(self, token) -> int:
        with self._write_lock:
            gen, live = self._begin()
            token.modify_index = gen
            self._acl_tokens.put(token.accessor_id, token, gen, live)
            self._acl_secret_idx.put(token.secret_id, token.accessor_id, gen, live)
            self._commit(gen, [("acl-token-upsert", token)])
            return gen

    def delete_acl_token(self, accessor_id: str) -> int:
        with self._write_lock:
            gen, live = self._begin()
            tok = self._acl_tokens.get_latest(accessor_id)
            self._acl_tokens.delete(accessor_id, gen, live)
            if tok is not None:
                self._acl_secret_idx.delete(tok.secret_id, gen, live)
            self._commit(gen, [("acl-token-delete", tok)])
            return gen

    def set_scheduler_configuration(self, cfg) -> int:
        """Replicated scheduler-config write (reference FSM
        ApplySchedulerConfigUpdate -> scheduler_config table): the
        operator's algorithm/preemption/pause settings survive leader
        failover because every replica applies this entry."""
        with self._write_lock:
            gen, live = self._begin()
            self._scheduler_config.put("config", cfg, gen, live)
            self._commit(gen, [("scheduler-config", cfg)])
            return gen

    def upsert_one_time_token(self, ott: dict) -> int:
        """Mint a one-time token row (reference
        state_store UpsertOneTimeToken): {"secret", "accessor_id",
        "expires"}. The secret is the key; the row never stores the
        underlying token's secret."""
        with self._write_lock:
            gen, live = self._begin()
            row = {"accessor_id": ott["accessor_id"],
                   "expires": float(ott["expires"])}
            self._one_time_tokens.put(ott["secret"], row, gen, live)
            self._commit(gen, [("ott-upsert", None)])
            return gen

    def take_one_time_token(self, secret: str, ts: float = None):
        """ATOMIC single-use exchange step: return-and-burn the row, or
        None when absent/expired. Check-then-delete outside the write
        lock would let two concurrent exchanges both win (reference
        one-time tokens are single-use by contract)."""
        ts = ts if ts is not None else self._clock()
        with self._write_lock:
            row = self._one_time_tokens.get_latest(secret)
            if row is None or ts >= row["expires"]:
                return None
            gen, live = self._begin()
            self._one_time_tokens.delete(secret, gen, live)
            self._commit(gen, [("ott-delete", None)])
            return dict(row)

    def delete_one_time_token(self, secret: str) -> int:
        """Burn a one-time token (exchange consumed it, or GC)."""
        with self._write_lock:
            gen, live = self._begin()
            self._one_time_tokens.delete(secret, gen, live)
            self._commit(gen, [("ott-delete", None)])
            return gen

    def gc_one_time_tokens(self, ts: float = None) -> int:
        """Expire unexchanged one-time tokens (reference core_sched.go
        expiredOneTimeTokenGC)."""
        ts = ts if ts is not None else self._clock()
        with self._write_lock:
            dead = [k for k, row in self._one_time_tokens.iterate(self._index)
                    if ts >= row["expires"]]
            if not dead:
                return 0
            gen, live = self._begin()
            for k in dead:
                self._one_time_tokens.delete(k, gen, live)
            self._commit(gen, [("ott-delete", None)])
            return len(dead)

    def gc_expired_acl_tokens(self, ts: float = None) -> int:
        """Drop tokens past their expiration (reference core_sched.go
        expiredACLTokenGC). `ts` rides the replicated command so
        followers replaying the log agree on what was expired."""
        ts = ts if ts is not None else self._clock()
        with self._write_lock:
            dead = [t for _, t in self._acl_tokens.iterate(self._index)
                    if getattr(t, "expiration_time", 0.0)
                    and ts >= t.expiration_time]
            if not dead:
                return 0
            gen, live = self._begin()
            for t in dead:
                self._acl_tokens.delete(t.accessor_id, gen, live)
                self._acl_secret_idx.delete(t.secret_id, gen, live)
            self._commit(gen, [("acl-token-delete", t) for t in dead])
            return len(dead)

    # --- variables (reference nomad/state/state_store_variables.go) ---

    def upsert_variable(self, var) -> int:
        with self._write_lock:
            self._require_namespace(var.namespace)
            gen, live = self._begin()
            key = (var.namespace, var.path)
            prev = self._variables.get_latest(key)
            var.create_index = prev.create_index if prev is not None else gen
            var.modify_index = gen
            self._variables.put(key, var, gen, live)
            self._commit(gen, [("variable-upsert", var)])
            return gen

    def delete_variable(self, path: str, namespace: str = "default") -> int:
        with self._write_lock:
            gen, live = self._begin()
            key = (namespace, path)
            var = self._variables.get_latest(key)
            self._variables.delete(key, gen, live)
            self._commit(gen, [("variable-delete", var)])
            return gen

    # --- GC (reference nomad/core_sched.go) ---

    def gc_terminal_allocs(self, before_index: int,
                           before_time: float = float("inf")) -> int:
        """Drop allocs with no remaining purpose: orphans of purged jobs,
        and explicitly-stopped (server-terminal) allocs that have also
        finished client-side. Failed allocs with desired=run are KEPT —
        they hold reschedule lineage for pending follow-up evals — and
        completed batch allocs are kept so finished work isn't re-run;
        both go with their job (reference core_sched.go ties alloc GC to
        eval/job GC for exactly these reasons)."""
        with self._write_lock:
            gen, live = self._begin()

            def gcable(a) -> bool:
                if a.modify_index >= before_index:
                    return False
                if (a.modify_time or 0) > before_time:
                    return False
                if self._jobs.get_latest((a.namespace, a.job_id)) is None:
                    return a.terminal_status() or a.server_terminal()
                return a.server_terminal() and a.client_terminal()

            dead_allocs = [a for _, a in self._allocs.iterate(gen) if gcable(a)]
            dead = [a.id for a in dead_allocs]
            dead_set = set(dead)
            # every gcable alloc is terminal, so none is usage-counting —
            # the usage rows never need adjusting here
            gc_events: list = []
            block_drops: Dict[str, list] = {}
            for a in dead_allocs:
                self._allocs.delete(a.id, gen, live)
                self._reap_services_for_terminal(a, gen, live, gc_events)
                # a deleted promoted row must not resurrect its block
                # position: mark it dropped in a new block version
                sep = a.id.rfind(BLOCK_SEP)
                if sep > 0:
                    block_drops.setdefault(a.id[:sep], []).append(
                        int(a.id[sep + 1:]))
            dead_blocks = set()
            for bid, positions in block_drops.items():
                block = self._alloc_blocks.get_latest(bid)
                if block is None:
                    continue
                block = block.with_dropped(positions)
                if block.live_size() <= 0:
                    self._alloc_blocks.delete(bid, gen, live)
                    if self._alloc_cols is not None:
                        self._alloc_cols.block_gone(bid, gen)
                    dead_blocks.add(bid)
                else:
                    self._alloc_blocks.put(bid, block, gen, live)
            # rebuild secondary indexes without the dead ids/blocks
            for table in (self._allocs_by_node, self._allocs_by_job, self._allocs_by_eval):
                for key, cell in list(table.iterate(gen)):
                    ids = [i for i in cons_iter(cell)
                           if not (i in dead_set if type(i) is not BlockRef
                                   else i.block_id in dead_blocks)]
                    # an earlier GC that emptied this key left a None
                    # cell (cons_from_iter of nothing); nothing to drop
                    if cell is not None and len(ids) != cell.length:
                        table.put(key, cons_from_iter(reversed(ids)), gen, live)
            self._commit(gen, gc_events + [("alloc-gc", dead)])
            return len(dead)
