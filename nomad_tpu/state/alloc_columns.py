"""The store's per-node allocation columns, kept as writes land.

One slot a live allocation row, one a block's live node row with the
count of its live positions that no row shadows, on the row numbers of
the store's dense per-node columns (state/store.py `_usage_rows`). A
slot holds what a system evaluation's victim columns need of the
allocation (tensor/cluster.py build_system_victims): its job's priority,
its allocated_vec, whether it holds ports or devices (`flag`) and port
numbers (`ports`), its group (namespace, job id, task group) interned,
that group's migrate max_parallel as its own job states it, and the
first eight bytes of its sort id (`key`: the allocation id, or a block's
prefix "<block id>." under which its positions sort as one unit).
"Live" is `_usage_apply`'s predicate, not terminal_status().

Built from the latest tables by the first system evaluation that reads
them (store.alloc_columns, `rebuild`): a store no such evaluation reads
pays nothing for them. From then on kept by the writers that move
`_usage_mat`, at the same points, inside their transaction; derived
like it, never in a dump or the log, and built anew on restore.

Readers take no lock. Every write stamps the node rows it touches with
its generation before it touches a slot, so a reader that finds a
node's stamp at or under its snapshot's index after it has gathered the
node's slots (and the store still holds this object and the same row
assignment) read the node as that snapshot sees it: no write since,
none half done. The arrays only grow: one that is replaced keeps what
it held, and a write after the swap stamps its nodes."""

from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional

import numpy as np

from ..structs.alloc import BLOCK_SEP, AllocBlock, Allocation


def max_parallel_of(job, tg_name: str) -> int:
    # imported here: the scheduler package imports the store
    from ..scheduler.preemption import max_parallel_of as of

    return of(job, tg_name)


# the priority of an allocation without a job: never a victim
NO_JOB = np.iinfo(np.int64).max


def sort_keys(ids: List[str]) -> np.ndarray:
    """The first eight bytes of each id as an unsigned big-endian number,
    zero-padded: where two keys differ they order their ids as str
    comparison does (UTF-8 keeps code point order)."""
    return np.array([i.encode() for i in ids], "S8").view(">u8").astype(
        np.uint64)


def unit_of(text: str) -> int:
    """A nonzero tag shared by a block's slot and every row whose id
    carries a block prefix ("<block id>."), 0 for an id without one."""
    sep = text.rfind(BLOCK_SEP)
    if sep < 0:
        return 0
    return hash(text[:sep + 1]) or 1


class AllocColumns:
    """Slots of live allocations by node row (module docstring)."""

    def __init__(self, row_of, assignment, dims: int, gen: int = 0,
                 cap: int = 1024):
        # node id -> row of the store's dense columns, created if new,
        # and the row assignment (_RowIndex) those rows belong to
        self._row_of = row_of
        self.assignment = assignment
        # the stamp of a node row no write has touched: the generation
        # the columns were built at
        self.base = gen
        self.node = np.full(cap, -1, np.int64)     # node row, -1 free
        self.count = np.zeros(cap, np.int64)       # live positions
        self.prio = np.zeros(cap, np.int64)
        self.vec = np.zeros((cap, dims))
        self.flag = np.zeros(cap, bool)
        self.ports = np.zeros(cap, bool)
        self.grp = np.zeros(cap, np.int64)
        self.mp = np.zeros(cap, np.int64)
        self.key = np.zeros(cap, np.uint64)
        self.unit = np.zeros(cap, np.int64)
        self.ref = np.empty(cap, object)           # alloc id / block id
        self.sub = np.full(cap, -1, np.int64)      # block node row, -1 row
        self.stamp = np.full(256, gen, np.int64)   # generation a node row
        self.top = 0
        self._free: List[int] = []
        self._rows: Dict[str, int] = {}            # alloc id -> slot
        self._blocks: Dict[str, np.ndarray] = {}   # block id -> slot a row
        # interned groups, freed when no slot names them
        self.groups: List[Optional[tuple]] = []
        self._gid: Dict[tuple, int] = {}
        self._gref: List[int] = []
        self._gfree: List[int] = []

    # -- plumbing --

    def _touch(self, rows, gen: int) -> None:
        """Stamp node rows with `gen`: first, before any slot moves."""
        need = int(np.max(rows)) + 1 if np.size(rows) else 0
        if need > len(self.stamp):
            self._grow_stamp(need)
        self.stamp[rows] = gen

    def _touch_row(self, row: int, gen: int) -> None:
        """_touch for one node row (an eviction's: no array round trip)."""
        if row >= len(self.stamp):
            self._grow_stamp(row + 1)
        self.stamp[row] = gen

    def _grow_stamp(self, need: int) -> None:
        grown = np.full(max(need, 2 * len(self.stamp)), self.base, np.int64)
        grown[: len(self.stamp)] = self.stamp
        self.stamp = grown

    def _take(self, n: int) -> np.ndarray:
        """n free slots (growing the arrays first when needed)."""
        free = self._free
        k = min(n, len(free))
        got = np.asarray(free[len(free) - k:], np.int64)
        del free[len(free) - k:]
        more = n - k
        if more:
            top = self.top
            if top + more > len(self.node):
                self._grow(top + more)
            got = np.concatenate([got, np.arange(top, top + more)])
            self.top = top + more
        return got

    def _grow(self, need: int) -> None:
        cap = max(need, 2 * len(self.node))
        for name in ("node", "count", "prio", "vec", "flag", "ports", "grp",
                     "mp", "key", "unit", "ref", "sub"):
            old = getattr(self, name)
            if old.dtype == object:
                new = np.empty((cap,) + old.shape[1:], object)
            else:
                new = np.full((cap,) + old.shape[1:],
                              -1 if name in ("node", "sub") else 0, old.dtype)
            new[: len(old)] = old
            setattr(self, name, new)

    def _group(self, key: tuple, n: int = 1) -> int:
        """The interned id of `key`, named by `n` more slots."""
        gid = self._gid.get(key)
        if gid is None:
            if self._gfree:
                gid = self._gfree.pop()
                self.groups[gid] = key
                self._gref[gid] = 0
            else:
                gid = len(self.groups)
                self.groups.append(key)
                self._gref.append(0)
            self._gid[key] = gid
        self._gref[gid] += n
        return gid

    def _ungroup(self, gid: int, n: int = 1) -> None:
        self._gref[gid] -= n
        if not self._gref[gid]:
            del self._gid[self.groups[gid]]
            self.groups[gid] = None
            self._gfree.append(gid)

    def _release(self, s: int) -> None:
        self.node[s] = -1
        self._ungroup(int(self.grp[s]))
        self._free.append(s)

    # -- rows --

    def _fill(self, got: np.ndarray, allocs: List[Allocation],
              rows) -> None:
        """Slots `got` take the rows `allocs`, on node rows `rows`,
        written as arrays."""
        ids = [a.id for a in allocs]
        self.node[got] = rows
        self.count[got] = 1
        self.prio[got] = [NO_JOB if a.job is None else a.job.priority
                          for a in allocs]
        self.vec[got] = [a.allocated_vec for a in allocs]
        ports = [bool(a.allocated_ports) for a in allocs]
        self.ports[got] = ports
        self.flag[got] = [p or bool(a.allocated_devices)
                          for p, a in zip(ports, allocs)]
        keys = [(a.namespace, a.job_id, a.task_group) for a in allocs]
        gids = {k: self._group(k, n) for k, n in Counter(keys).items()}
        self.grp[got] = [gids[k] for k in keys]
        mp: Dict[tuple, int] = {}
        for a in allocs:
            at = (id(a.job), a.task_group)
            if at not in mp:
                mp[at] = max_parallel_of(a.job, a.task_group)
        self.mp[got] = [mp[id(a.job), a.task_group] for a in allocs]
        self.key[got] = sort_keys(ids)
        self.unit[got] = [unit_of(i) for i in ids]
        self.ref[got] = ids
        self.sub[got] = -1
        self._rows.update(zip(ids, got.tolist()))

    def transition(self, prev: Optional[Allocation], new: Optional[Allocation],
                   live_before: bool, live_after: bool, gen: int,
                   blocks) -> None:
        """One allocation id's latest version goes from `prev` to `new`
        (either None; `prev` a block position's virtual row on the
        position's first write), each live or not. `blocks` is the block
        table's latest lookup, for the position's node row."""
        if not live_before and not live_after:
            return
        s = self._rows.get(prev.id) if live_before else None
        if live_before and s is None:
            self._shadow_position(prev.id, gen, blocks)
        if live_after:
            row = self._row_of(new.node_id)
            self._touch_row(row, gen)
            if s is None:
                got = self._take(1)
            else:
                self._touch_row(int(self.node[s]), gen)
                self._ungroup(int(self.grp[s]))
                got = np.array([s])
            self._fill(got, [new], [row])
        elif s is not None:
            self._touch_row(int(self.node[s]), gen)
            del self._rows[prev.id]
            self._release(s)

    def rows_added(self, allocs: List[Allocation], gen: int) -> None:
        """First inserts of rows (no version before them), in one pass."""
        live = [a for a in allocs if not a.terminal_status()]
        if not live:
            return
        rows = np.fromiter(map(self._row_of, (a.node_id for a in live)),
                           np.int64, len(live))
        self._touch(rows, gen)
        self._fill(self._take(len(live)), live, rows)

    def _shadow_position(self, alloc_id: str, gen: int, blocks) -> None:
        """A block position gets a row (or goes): its node row's slot
        counts one position less, and goes at none."""
        sep = alloc_id.rfind(BLOCK_SEP)
        slots = self._blocks.get(alloc_id[:sep]) if sep > 0 else None
        block = blocks(alloc_id[:sep]) if slots is not None else None
        if block is None or not alloc_id[sep + 1:].isdigit():
            return
        m = block.row_for_pos(int(alloc_id[sep + 1:]))
        s = int(slots[m])
        if s < 0:
            return
        self._touch_row(int(self.node[s]), gen)
        self.count[s] -= 1
        if self.count[s] <= 0:
            slots[m] = -1
            self._release(s)

    # -- blocks --

    def block_added(self, block: AllocBlock, gen: int,
                    counts: Optional[np.ndarray] = None) -> None:
        """A block's live node rows, one slot each with its count of live
        positions (`counts`, one a node row: the block's own unless
        given; a row at 0 gets none), written as arrays."""
        self.block_gone(block.id, gen)
        live = (block.counts if counts is None else counts) > 0
        if block.rejected_rows:
            live[list(block.rejected_rows)] = False
        at = np.flatnonzero(live)
        if not len(at):
            return
        node_ids = block.node_ids
        rows = np.fromiter(map(self._row_of,
                               (node_ids[m] for m in at.tolist())),
                           np.int64, len(at))
        self._touch(rows, gen)
        got = self._take(len(at))
        job = block.job
        self.node[got] = rows
        self.count[got] = (block.counts if counts is None else counts)[at]
        self.prio[got] = NO_JOB if job is None else job.priority
        self.vec[got] = block.allocated_vec
        self.ports[got] = block.ports is not None
        self.flag[got] = block.ports is not None or block.devices is not None
        self.grp[got] = self._group(
            (block.namespace, block.job_id, block.task_group), len(got))
        self.mp[got] = max_parallel_of(job, block.task_group)
        prefix = block.id + BLOCK_SEP
        self.key[got] = sort_keys([prefix])[0]
        self.unit[got] = unit_of(prefix)
        self.ref[got] = block.id
        self.sub[got] = at
        slots = np.full(len(node_ids), -1, np.int64)
        slots[at] = got
        self._blocks[block.id] = slots

    def block_gone(self, block_id: str, gen: int) -> None:
        """The block left the table: whatever slots it still has go."""
        slots = self._blocks.pop(block_id, None)
        if slots is None:
            return
        held = slots[slots >= 0]
        if len(held):
            self._touch(self.node[held], gen)
            self.node[held] = -1
            gids, n = np.unique(self.grp[held], return_counts=True)
            for gid, k in zip(gids.tolist(), n.tolist()):
                self._ungroup(gid, k)
            self._free.extend(held.tolist())

    # -- restore --

    def rebuild(self, allocs, blocks) -> None:
        """Every slot from the latest rows and blocks (`allocs`: rows,
        `blocks`: blocks), stamped with the generation the columns are
        built at."""
        gen = self.base
        allocs = list(allocs)
        self.rows_added(allocs, gen)
        shadowed: Dict[str, set] = {}
        for a in allocs:
            sep = a.id.rfind(BLOCK_SEP)
            if sep > 0:
                shadowed.setdefault(a.id[:sep], set()).add(a.id[sep + 1:])
        for block in blocks:
            gone = np.asarray(sorted(block.dropped | {
                int(p) for p in shadowed.get(block.id, ()) if p.isdigit()}),
                np.int64)
            gone = gone[gone < block.size]
            counts = np.asarray(block.counts, np.int64) - np.bincount(
                np.searchsorted(block.offsets(), gone, side="right") - 1,
                minlength=len(block.node_ids))
            self.block_added(block, gen, counts)
