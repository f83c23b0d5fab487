"""nomadstate: device-resident incremental cluster state.

Every scheduling round used to rebuild the (N, D) usage tensor from a
host snapshot — an O(N) gather + device_put per eval that at C2M scale
is the wall after the solver's solve/apply overlap. This module makes
the warm-path tensor build O(allocs changed) instead: one
:class:`IncrementalFeed` per store subscribes to the commit stream's
Allocation/Node topics (the same contract ``analysis/shadow.py``'s
sanitizer machine-checks) and folds each delta into a persistent host
base plus a compact device-delta log, so

- ``ClusterTensors.refresh_usage`` takes the fed base as a shared
  read-only view (zero per-round host work) instead of re-gathering
  the store's usage matrix;
- the bulk solver service's resync takes a device-RESIDENT twin of the
  base (sharded ``NamedSharding(P("nodes", None))``, same layout as
  the solve carry) and folds its open-ledger entries with ONE jitted
  scatter-add launch instead of shipping a rebuilt O(N) host array.

Delta-folding semantics are ``state/deltas.py``'s — the single
implementation shared with the shadow sanitizer: columnar AllocBlock
expansion (held by reference here, never expanded to per-position
rows), promoted-row override, GC pops, truncation→resync. The feed is
PULL-model: deltas drain at build/verify time under the feed's own
lock, never on the store's commit path, so event consumption costs the
scheduler nothing until it needs fresh state.

Consistency contract (the part chaos + NOMAD_TPU_SAN=1 enforce):

- RESYNC rebuilds from one MVCC snapshot — base rows from the
  gen-bounded ``_node_usage`` table, row/block bookkeeping from
  gen-bounded table iteration — and pins ``position = snap.index``.
  Any drained event with ``index <= position`` is already inside the
  base and is discarded; events beyond it fold incrementally. Ring
  truncation, the ``restore`` sentinel, node deletion, and any parity
  mismatch all route back through this path: resync is the repair
  story, never incremental patching.
- PARITY: every K builds under ``NOMAD_TPU_SAN=1`` (and on demand from
  the chaos invariant sweep / the state smoke) the feed drains to a
  write-lock-consistent index and digests its base — device twins
  included — against a fresh rebuild from the same gen-bounded tables.
  Resource vectors are integral, so f64 folds commute exactly and the
  compare demands bit-equality, no tolerance.
- A store with no feed attached, or a resync that failed, gets the
  exact per-round rebuild: ``base_for`` / ``feed_for`` answer None and
  every call site falls back on that.

The shared base view is refreshed in place by later drains, so a solve
that kept the view may observe newer committed usage mid-read — the
same freshness the legacy ``_usage_mat`` gather already leaks by
design; the serialized plan applier owns correctness either way.
"""

from __future__ import annotations

import _thread
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ..obs import TRACER
from ..state.deltas import ALLOC_ROW_KINDS
from ..structs.resources import RESOURCE_DIMS

_REAL_LOCK = _thread.allocate_lock

FEED_TOPICS = {"Allocation": ["*"], "Node": ["*"]}

# builds between base-vs-rebuild parity digests when the sanitizer is on
PARITY_EVERY = 64
# device-delta batches pad to powers of two from this floor so the warm
# path cycles a handful of compiled scatter shapes
SCATTER_FLOOR = 8
# a twin lagging more than one full base behind re-uploads instead of
# scattering; a log grown past this multiple drops every twin and resets
LOG_CAP_MULT = 4

# shapes already compiled for the delta scatter / resync fold launches
# (tensor/solver.warm_launch discipline: warm shapes compile nothing)
_STATE_WARM: set = set()


def _pad_bucket(n: int) -> int:
    out = SCATTER_FLOOR
    while out < n:
        out *= 2
    return out


# -- jitted scatter (single-device arm; the sharded twin lives in
#    tensor/sharding.make_state_scatter_sharded) -------------------------

_SCATTER_JIT = None


def _scatter_fn(donate: bool):
    """used.at[idx].add(delta): ONE launch applies a whole delta batch.
    Padding rows carry (idx=0, delta=0) — an exact no-op add (usage
    values are integral and never -0.0)."""
    global _SCATTER_JIT
    if _SCATTER_JIT is None:
        import jax

        def state_scatter(used, idx, delta):
            return used.at[idx].add(delta)

        def state_fold(used, idx, delta):
            return used.at[idx].add(delta)

        _SCATTER_JIT = (jax.jit(state_scatter, donate_argnums=(0,)),
                        jax.jit(state_fold))
    return _SCATTER_JIT[0 if donate else 1]


class Violation:
    __slots__ = ("kind", "message")

    def __init__(self, kind: str, message: str):
        self.kind = kind
        self.message = message

    def render(self) -> str:
        return f"[{self.kind}] {self.message}"


class _Twin:
    """One device-resident f32 copy of the base (per placement layout:
    single-device, or one per mesh), caught up to `cursor` entries of
    the epoch's delta log."""

    __slots__ = ("arr", "cursor")

    def __init__(self, arr, cursor: int):
        self.arr = arr
        self.cursor = cursor


class _Epoch:
    """Feed state bound to one node LAYOUT (ordered id tuple). A static
    version bump with identical membership keeps the epoch — content
    edits don't move usage rows; membership changes resync."""

    __slots__ = ("layout", "node_index", "n_pad", "base", "base_view",
                 "position", "rows", "blocks", "gc_dropped", "stale",
                 "devlog", "twins", "static_ref")

    def __init__(self, layout: tuple, node_index: Dict[str, int],
                 n_pad: int, position: int):
        self.layout = layout
        self.node_index = node_index
        self.n_pad = n_pad
        self.base = np.zeros((n_pad, RESOURCE_DIMS))
        self.base_view = self.base.view()
        self.base_view.setflags(write=False)
        self.position = position
        # alloc id -> (node_id, counted, vec) for REAL rows only; block
        # positions stay columnar (virtual prev computed on demand)
        self.rows: Dict[str, tuple] = {}
        self.blocks: Dict[str, object] = {}
        # per block id: positions GC'd after our held (insert-time) ref
        self.gc_dropped: Dict[str, Set[int]] = {}
        self.stale = False
        # append-only (row, f64 delta vec) log the device twins consume
        self.devlog: List[Tuple[int, np.ndarray]] = []
        self.twins: Dict[object, _Twin] = {}
        self.static_ref = None


class _Drain:
    """One drain of a feed: a ``feed.drain`` span around work its caller
    does under the feed's lock, counted in the feed's ``drains`` and
    ``events_drained`` whether the tracer is on or not. The span's args:
    ``events`` taken from the subscription, ``rows`` folded into the
    base, and ``resync=True`` when the base was rebuilt."""

    __slots__ = ("feed", "span", "mark")

    def __init__(self, feed: "IncrementalFeed"):
        self.feed = feed

    def __enter__(self):
        f = self.feed
        self.mark = (f._events_taken, f._deltas_applied, f._resyncs)
        self.span = TRACER.span("feed.drain", device=True)
        self.span.__enter__()
        return self

    def __exit__(self, *exc):
        f = self.feed
        events = f._events_taken - self.mark[0]
        f._drains += 1
        f._events_drained += events
        self.span.set(events=events, rows=f._deltas_applied - self.mark[1])
        if f._resyncs != self.mark[2]:
            self.span.set(resync=True)
        return self.span.__exit__(*exc)


class IncrementalFeed:
    """Delta-fed usage state for one (store, broker) pair. All entry
    points take ``self._lock``; nothing here runs on the commit path.
    The two that drain for a reader (``base_for``, ``device_used``)
    record the wait for the lock as a ``feed.lock_wait`` span and the
    work under it as a ``feed.drain`` span (:class:`_Drain`)."""

    def __init__(self, store, broker, tracker: "StateTracker"):
        self.store = store
        self.tracker = tracker
        self.sub = broker.subscribe(dict(FEED_TOPICS))
        self._lock = _REAL_LOCK()
        self._epoch: Optional[_Epoch] = None
        self._builds = 0
        self._fast_hits = 0
        self._resyncs = 0
        self._deltas_applied = 0
        self._parity_checks = 0
        # events taken from the subscription, and the drains that took
        # them for a reader (stats()["drains"] / ["events_drained"])
        self._events_taken = 0
        self._drains = 0
        self._events_drained = 0
        self._gauge_pub = None
        # moves whenever committed usage may have gone DOWN under a
        # consumer that chains its own copy (the bulk solver's carry,
        # tensor/solver.py): once a folded negative delta, once a
        # resync, whose discarded backlog may have held some. Read
        # without the lock: a consumer only compares it with the value
        # its copy was rebuilt at
        self._free_epoch = 0

    # -- public surface ------------------------------------------------

    def base_for(self, static) -> Optional[np.ndarray]:
        """The fed usage base aligned to `static`'s row order, as a
        read-only (n_pad, D) f64 view — or None (resync failed), which
        means: do the full build."""
        if static is None:
            return None
        with TRACER.span("feed.lock_wait", device=True):
            self._lock.acquire()
        try:
            with _Drain(self):
                self._builds += 1
                ep = self._epoch_for_locked(static)
                if ep is None:
                    return None
                self._fast_hits += 1
                if (self.tracker.san_active
                        and self._builds % PARITY_EVERY == 0):
                    self._verify_locked()
                    ep = self._epoch
                    if ep is None or ep.stale:
                        return None
                self._gauges()
                return ep.base_view
        finally:
            self._lock.release()

    def device_used(self, static, mesh=None):
        """Device-resident f32 twin of the base (sharded over `mesh`
        when given), flushed through one scatter launch. None when the
        feed can't serve this static — caller falls back to host."""
        if static is None:
            return None
        with TRACER.span("feed.lock_wait", device=True):
            self._lock.acquire()
        try:
            with _Drain(self):
                ep = self._epoch_for_locked(static)
            if ep is None:
                return None
            return self._twin_locked(ep, mesh).arr
        finally:
            self._lock.release()

    def free_epoch(self) -> int:
        """How often this feed has seen committed usage fall (a job
        stopped or purged, an allocation gone terminal) since it was
        attached; a resync counts as one. Nothing is drained here: the
        worker's tensor build drains before it asks for a solve."""
        return self._free_epoch

    def force_verify(self) -> bool:
        """Drain + parity-digest now (chaos sweep, state smoke,
        teardowns). Builds an epoch over the store's node set first if
        none exists, so follower replicas verify meaningfully."""
        with self._lock:
            if self._epoch is None or self._epoch.stale:
                snap = self.store.snapshot()
                try:
                    ids = sorted(n.id for n in snap.nodes())
                finally:
                    snap.close()
                layout = tuple(ids)
                index = {nid: i for i, nid in enumerate(ids)}
                n_pad = _pad_pow2(max(len(ids), 1))
                if not self._resync_locked(layout, index, n_pad):
                    return True     # nothing to verify against
            return self._verify_locked()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "builds": self._builds,
                "fast_hits": self._fast_hits,
                "resyncs": self._resyncs,
                "deltas_applied": self._deltas_applied,
                "parity_checks": self._parity_checks,
                "drains": self._drains,
                "events_drained": self._events_drained,
            }

    # -- epoch lifecycle ----------------------------------------------

    def _epoch_for_locked(self, static) -> Optional[_Epoch]:
        ep = self._epoch
        if ep is not None and not ep.stale:
            if ep.static_ref is static:
                self._drain_locked(ep)
                ep = self._epoch          # drain may have resynced
            elif ep.layout == tuple(static.node_index):
                # version bump, same membership/order: adopt the new
                # static, keep the base (usage rows didn't move)
                ep.static_ref = static
                ep.node_index = static.node_index
                self._drain_locked(ep)
                ep = self._epoch
            else:
                ep = None
        if ep is None or ep.stale:
            layout = tuple(static.node_index)
            if not self._resync_locked(layout, static.node_index,
                                       static.n_pad):
                return None
            ep = self._epoch
            ep.static_ref = static
        return ep

    def _resync_locked(self, layout: tuple, node_index: Dict[str, int],
                       n_pad: int) -> bool:
        """Rebuild everything from one MVCC snapshot. Every event with
        index <= snap.index is inside the rebuilt base; the global
        discard-by-position rule in _drain_locked makes that airtight
        regardless of commit-listener interleaving."""
        # discard the backlog first: all of it predates the snapshot we
        # are about to take, so all of it is (or will be) in the base
        evs = self.sub.next_events(timeout=0)
        self.sub.truncated = False
        self._events_taken += len(evs)
        store = self.store
        snap = store.snapshot()
        try:
            ep = _Epoch(layout, node_index, n_pad, snap.index)
            gen = snap.index
            usage = store._node_usage
            for nid, i in node_index.items():
                vec = usage.get(nid, gen)
                if vec is not None:
                    ep.base[i] = vec
            for aid, a in store._allocs.iterate(gen):
                ep.rows[aid] = (a.node_id, not a.terminal_status(),
                                a.allocated_vec)
            for bid, block in store._alloc_blocks.iterate(gen):
                ep.blocks[bid] = block
        except Exception:
            self._epoch = None
            return False
        finally:
            snap.close()
        self._epoch = ep
        self._resyncs += 1
        self._free_epoch += 1
        self._gauges()
        return True

    # -- drain + fold --------------------------------------------------

    def _drain_locked(self, ep: _Epoch) -> None:
        evs = self.sub.next_events(timeout=0)
        self._events_taken += len(evs)
        if self.sub.truncated:
            # lapped ring or restore sentinel: the contract answer is a
            # full resync, never incremental patching
            self.sub.truncated = False
            self._resync_locked(ep.layout, ep.node_index, ep.n_pad)
            if self._epoch is not None:
                self._epoch.static_ref = ep.static_ref
            return
        for e in evs:
            if e.index <= ep.position:
                continue        # already inside the resync base
            self._fold(ep, e)
        # ep.position is the resync FLOOR, never advanced per event:
        # one commit emits many events sharing one index (and a drain
        # can catch a commit's topic shards half-published), so
        # advancing on the first would discard its siblings. Delivery
        # past the floor is exactly-once by the subscription cursor.

    def _fold(self, ep: _Epoch, e) -> None:
        kind = e.type
        p = e.payload
        if kind in ALLOC_ROW_KINDS:
            self._fold_alloc_row(ep, p)
        elif kind == "alloc-block-upsert":
            self._fold_block(ep, p)
        elif kind == "alloc-gc":
            self._fold_gc(ep, p)
        elif kind == "node-delete":
            if p is not None and p.id in ep.node_index:
                # membership changed mid-epoch; the next build's static
                # carries the new layout — serve nothing until then
                ep.stale = True
        # other NODE_KINDS: content-only, usage rows don't move

    def _fold_alloc_row(self, ep: _Epoch, a) -> None:
        new = (a.node_id, not a.terminal_status(), a.allocated_vec)
        prev = ep.rows.get(a.id)
        if prev is None:
            prev = self._virtual_row(ep, a.id)
        ep.rows[a.id] = new
        if prev is not None:
            pn, pc, pv = prev
            if (pc and new[1] and pn == new[0] and pv is not None
                    and new[2] is not None
                    and np.array_equal(pv, new[2])):
                return          # annotation-only rewrite (store predicate)
            if pc and pv is not None:
                self._add(ep, pn, pv, -1.0)
        if new[1] and new[2] is not None:
            self._add(ep, new[0], new[2], 1.0)

    def _fold_block(self, ep: _Epoch, block) -> None:
        if block.id in ep.blocks:
            ep.blocks[block.id] = block     # defensive; store emits once
            return
        ep.blocks[block.id] = block
        vec = block.allocated_vec
        for m in block.live_rows():
            c = int(block.counts[m])
            self._add(ep, block.node_ids[m],
                      vec * c if c != 1 else vec, 1.0)

    def _fold_gc(self, ep: _Epoch, ids) -> None:
        from ..structs.alloc import BLOCK_SEP
        for aid in ids:
            # every gcable alloc is terminal → never usage-counting: GC
            # pops bookkeeping, moves no resources (store contract)
            ep.rows.pop(aid, None)
            sep = aid.rfind(BLOCK_SEP)
            if sep > 0:
                try:
                    pos = int(aid[sep + 1:])
                except ValueError:
                    continue
                ep.gc_dropped.setdefault(aid[:sep], set()).add(pos)

    def _virtual_row(self, ep: _Epoch, aid: str) -> Optional[tuple]:
        """A block position's implied row — the feed-side mirror of
        store._block_alloc_fallback over our held (insert-time) block
        ref, with gc_dropped compensating for the store's quiet
        with_dropped re-puts."""
        from ..structs.alloc import BLOCK_SEP
        sep = aid.rfind(BLOCK_SEP)
        if sep < 0:
            return None
        block = ep.blocks.get(aid[:sep])
        if block is None:
            return None
        try:
            pos = int(aid[sep + 1:])
        except ValueError:
            return None
        if pos < 0 or pos >= block.size or not block.visible(pos):
            return None
        if pos in ep.gc_dropped.get(aid[:sep], ()):
            return None
        m = block.row_for_pos(pos)
        return (block.node_ids[m], True, block.allocated_vec)

    def _add(self, ep: _Epoch, node_id: str, vec, sign: float) -> None:
        row = ep.node_index.get(node_id)
        if row is None:
            return
        if sign > 0:
            delta = vec[:RESOURCE_DIMS]
        else:
            delta = -vec[:RESOURCE_DIMS]
            self._free_epoch += 1
        ep.base[row] += delta
        self._deltas_applied += 1
        if ep.twins:
            ep.devlog.append((row, delta))
            if len(ep.devlog) > LOG_CAP_MULT * ep.n_pad:
                # runaway log with no consumer draining it: cheaper to
                # re-upload the base than to replay this much
                ep.devlog.clear()
                ep.twins.clear()

    # -- device twins --------------------------------------------------

    def _twin_locked(self, ep: _Epoch, mesh) -> _Twin:
        import jax

        key = mesh if mesh is not None else None
        tw = ep.twins.get(key)
        if tw is not None and len(ep.devlog) - tw.cursor > ep.n_pad:
            tw = None               # lagged past a full base: re-upload
        if tw is None:
            arr = np.ascontiguousarray(ep.base, dtype=np.float32)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                arr = jax.device_put(
                    arr, NamedSharding(mesh, P("nodes", None)))
            else:
                arr = jax.device_put(arr)
            tw = ep.twins[key] = _Twin(arr, len(ep.devlog))
        elif tw.cursor < len(ep.devlog):
            tw.arr = self._flush_twin(ep, tw, mesh)
            tw.cursor = len(ep.devlog)
        if all(t.cursor == len(ep.devlog) for t in ep.twins.values()):
            for t in ep.twins.values():
                t.cursor = 0
            ep.devlog.clear()
        return tw

    def _flush_twin(self, ep: _Epoch, tw: _Twin, mesh):
        """ONE donated scatter launch applies every pending delta to
        this twin. Pad rows (idx 0, delta 0) are exact no-ops."""
        import jax

        from .solver import warm_launch

        entries = ep.devlog[tw.cursor:]
        bucket = _pad_bucket(len(entries))
        d = RESOURCE_DIMS
        idx = np.zeros(bucket, dtype=np.int32)
        delta = np.zeros((bucket, d), dtype=np.float32)
        for i, (row, vec) in enumerate(entries):
            idx[i] = row
            delta[i] = vec
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .sharding import make_state_scatter_sharded

            n_dev = int(np.prod(mesh.devices.shape))
            fn = make_state_scatter_sharded(mesh)
            rep = NamedSharding(mesh, P())
            idx = jax.device_put(idx, rep)
            delta = jax.device_put(delta, rep)
            key = ("statescatter-sh", ep.n_pad, d, bucket, n_dev)
        else:
            fn = _scatter_fn(donate=True)
            idx, delta = jax.device_put((idx, delta))
            key = ("statescatter", ep.n_pad, d, bucket)
        with warm_launch(fn, key, _STATE_WARM):
            return fn(tw.arr, idx, delta)

    # -- parity --------------------------------------------------------

    def _verify_locked(self) -> bool:
        """Digest base (+ flushed twins) against a fresh gen-bounded
        rebuild. Draining under the store's write lock pins an index at
        which the subscription is provably complete, so the compare is
        exact — no retries, no tolerance. Mismatch records a violation
        and forces a resync (repair, never poison the build path)."""
        import jax

        ep = self._epoch
        if ep is None or ep.stale:
            return True
        store = self.store
        with store._write_lock:
            evs = self.sub.next_events(timeout=0)
            truncated = self.sub.truncated
            self.sub.truncated = False
            snap = store.snapshot()
        try:
            self._events_taken += len(evs)
            if truncated:
                self._resync_locked(ep.layout, ep.node_index, ep.n_pad)
                if self._epoch is not None:
                    self._epoch.static_ref = ep.static_ref
                return True
            for e in evs:
                if e.index <= ep.position:
                    continue    # resync floor; never advanced per event
                self._fold(ep, e)
            gen = snap.index
            n = len(ep.layout)
            truth = np.zeros((ep.n_pad, RESOURCE_DIMS))
            usage = store._node_usage
            for nid, i in ep.node_index.items():
                vec = usage.get(nid, gen)
                if vec is not None:
                    truth[i] = vec
        finally:
            snap.close()
        self._parity_checks += 1
        ok = np.array_equal(ep.base, truth)
        if ok:
            for key, tw in list(ep.twins.items()):
                if tw.cursor < len(ep.devlog):
                    continue        # unflushed: checked after next flush
                got = np.asarray(jax.device_get(tw.arr))
                if not np.array_equal(got, ep.base.astype(np.float32)):
                    ok = False
                    self.tracker.record(Violation(
                        "state-divergence",
                        f"device twin diverged from host base "
                        f"(mesh={'yes' if key is not None else 'no'}, "
                        f"n={n}, index {gen})"))
                    break
        else:
            bad = [ep.layout[i] for i in
                   np.nonzero(~np.all(ep.base[:n] == truth[:n],
                                      axis=1))[0][:8]]
            self.tracker.record(Violation(
                "state-divergence",
                f"incremental base diverged from snapshot rebuild at "
                f"index {gen} ({self._resyncs} resync(s), "
                f"{self._deltas_applied} delta(s)): node(s) {bad}"))
        if not ok:
            self._epoch = None      # force resync: repair, don't wedge
        self._gauges()
        return ok

    def _gauges(self) -> None:
        # base_for calls this on EVERY fast hit: skip the (process-
        # global-locked) registry writes unless a counter moved, or 24
        # racing workers convoy on the registry lock inside the
        # tensor_build span
        vals = (self._resyncs, self._deltas_applied, self._parity_checks)
        if vals == self._gauge_pub:
            return
        self._gauge_pub = vals
        from ..core.metrics import REGISTRY
        REGISTRY.set_gauge("nomad.state.resyncs", float(self._resyncs))
        REGISTRY.set_gauge("nomad.state.deltas_applied",
                           float(self._deltas_applied))
        REGISTRY.set_gauge("nomad.state.parity_checks",
                           float(self._parity_checks))


def _pad_pow2(n: int, floor: int = 8) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


class StateTracker:
    """Registry of incremental feeds + their parity violations. Mirrors
    the shadow tracker's surface so conftest/chaos treat both prongs
    uniformly; unlike the shadow, feeds attach in PRODUCTION (the kill
    switch gates use, not attach) — san_active only arms the periodic
    parity digests."""

    def __init__(self):
        self.san_active = False
        self._ilock = _REAL_LOCK()
        self.feeds: List[IncrementalFeed] = []
        self.violations: List[Violation] = []

    def install(self) -> None:
        self.san_active = True

    def uninstall(self) -> None:
        self.san_active = False

    def attach(self, store, broker) -> Optional[IncrementalFeed]:
        # unwrap write facades (raft's RaftStore): the feed must key on
        # the snapshot-owning StateStore, because consumers find it via
        # snapshot._store identity (feed_for)
        store = getattr(store, "_store", store)
        existing = getattr(store, "_incremental_feed", None)
        if existing is not None:
            return existing
        feed = IncrementalFeed(store, broker, self)
        store._incremental_feed = feed
        with self._ilock:
            self.feeds.append(feed)
        return feed

    def record(self, v: Violation) -> None:
        with self._ilock:
            self.violations.append(v)

    def verify_all(self) -> List[str]:
        """Force a parity digest on every feed; rendered violations
        after. The chaos invariant sweep's view of the device state."""
        with self._ilock:
            feeds = list(self.feeds)
        for feed in feeds:
            feed.force_verify()
        return [v.render() for v in self.violations]

    def check(self) -> None:
        if self.violations:
            raise AssertionError(
                "nomadstate violations:\n"
                + "\n".join(v.render() for v in self.violations))

    def stats(self) -> Dict[str, int]:
        with self._ilock:
            feeds = list(self.feeds)
        out = {"feeds": len(feeds), "builds": 0, "fast_hits": 0,
               "resyncs": 0, "deltas_applied": 0, "parity_checks": 0,
               "drains": 0, "events_drained": 0}
        for f in feeds:
            for k, v in f.stats().items():
                out[k] += v
        return out

    def report(self) -> str:
        s = self.stats()
        lines = [
            f"nomadstate: {len(self.violations)} violation(s); "
            f"feeds={s['feeds']} builds={s['builds']} "
            f"fast_hits={s['fast_hits']} resyncs={s['resyncs']} "
            f"deltas={s['deltas_applied']} parity={s['parity_checks']}"]
        for v in self.violations:
            lines.append("  " + v.render())
        return "\n".join(lines)


# -- module-level surface (server wiring + conftest + chaos) --------------

GLOBAL = StateTracker()


def install() -> None:
    GLOBAL.install()


def uninstall() -> None:
    GLOBAL.uninstall()


def maybe_attach(store, broker) -> Optional[IncrementalFeed]:
    """Server-side hook next to shadow.maybe_attach: one feed per
    (store, broker) pair, idempotent."""
    return GLOBAL.attach(store, broker)


def feed_for(store) -> Optional[IncrementalFeed]:
    return getattr(store, "_incremental_feed", None) if store is not None \
        else None


def device_used_fn(store, static):
    """A (mesh) -> device array | None closure for the bulk solver's
    resync, or None when no feed serves this store."""
    feed = feed_for(store)
    if feed is None or static is None:
        return None

    def fn(mesh=None):
        return feed.device_used(static, mesh)

    return fn


def free_epoch_fn(store):
    """() -> the store's feed's free epoch (`IncrementalFeed.free_epoch`)
    for the bulk solver's stale-carry test, or None without a feed."""
    feed = feed_for(store)
    return None if feed is None else feed.free_epoch


def violations() -> List[Violation]:
    return list(GLOBAL.violations)


def check() -> None:
    GLOBAL.check()
