"""Batched bulk-solve service: one device launch for many evals.

Every launch pays a fixed dispatch + readback cost whatever its size
(its value on the current chip: chip_smoke.py prints it, PERF.md
"Bring-up on the v5e" records it), and at C2M scale (500 evals x 4,000
allocs) a launch per eval multiplies it by 500. Racing scheduler
workers therefore don't talk to the device directly on the bulk path:
they enqueue solve requests here and block on a future, while ONE
service thread batches compatible requests into a single
kernels.solve_bulk_multi launch whose usage carry never leaves the
device between launches. Per eval, one ask row + scalars go in and one
(N,) int16 counts row comes out; the fixed cost amortizes across the
batch. Batching is demand-driven: while a launch is in flight, newly
arriving requests queue up and form the next batch (backpressure, not
timers, sets the batch size).

This is the "solver service" split SURVEY.md §2.5 calls for: cheap
local control-plane work on the host, batched dense solves on the
accelerator, one serialized commit point (the plan applier) unchanged.

Correctness contract: the device usage carry is an optimistic overlay
(base = store usage at the last resync, plus every solve since), and
the serialized plan applier remains the gate — it re-verifies every
placement against real state (core/plan_apply.py) exactly as for
host-solved plans, so drift can only cost throughput, never
correctness. Drift is then actively repaired instead of tolerated:

- every solve opens an in-flight LEDGER entry (per-node counts + ask);
- the scheduler invokes a plan post-apply hook (structs/plan.py
  post_apply_hooks) -> confirm(): a fully-committed solve just closes
  its entry (its usage is now in the store); a solve with rejected
  nodes closes its entry too and marks the carry STALE (why: confirm());
- resync (every RESYNC_SOLVES solves, on node-set change, after a
  rejection, or after a committed FREE) rebuilds the carry as committed
  store usage PLUS the still-open ledger entries, so in-flight work is
  never dropped from the overlay and a rejected placement's phantom
  never outlives one launch;
- a free is usage the carry keeps and the store no longer has (a job
  stopped or purged, an allocation gone terminal): the incremental feed
  counts the ones it folds (IncrementalFeed.free_epoch) and a dispatch
  whose carry was rebuilt at another count resyncs first. Without that
  a backlog submitted to a cluster that was just emptied is solved
  against a full one and blocks with nothing left to unblock it.

Without the ledger the carry both leaks rejected-placement phantoms
(solve shortfalls -> blocked-eval retry storms as the cluster fills)
and forgets in-flight solves at resync (double-booking -> rejection
bursts); measured in-round, that fed a tail where the last 10% of a
2M-alloc run took longer than the first 90%.
"""

from __future__ import annotations

import logging
import queue
import threading
from concurrent.futures import Future
from typing import List, Optional

import numpy as np

from ..core.metrics import REGISTRY
from ..obs import RECORDER, TRACER

logger = logging.getLogger("nomad_tpu.solver")

_STOP = object()


def warm_launch(fn, shape_key, warm: set):
    """Shape-keyed launch window around one kernel launch: a warm shape
    runs under a hard jit_guard.no_retrace window (zero new compiles,
    implicit transfers raise), a cold shape may compile once and then
    marks itself warm. Either way the launch lands in the nomadjit
    ledger (no-op unless NOMAD_TPU_SAN=1) with its warm/cold standing.

    Callers jax.device_put EVERY argument first — committed jax.Arrays
    and bare numpy hit different jit cache entries, so a mixed diet
    would read as a retrace — and read back through a single
    jax.device_get, the launch's only host sync. Shared by the placer's
    per-eval launch sites and the incremental state's delta scatters."""
    import contextlib

    from ..analysis import launch_ledger
    from .jit_guard import count_compiles, no_retrace

    is_warm = shape_key in warm

    @contextlib.contextmanager
    def _window():
        name = getattr(fn, "__name__", str(fn))
        with launch_ledger.window(name, key=shape_key, warm=is_warm):
            if is_warm:
                with no_retrace(fn):
                    yield
            else:
                with count_compiles(fn):
                    yield
                warm.add(shape_key)

    return _window()


class BatchContext:
    """Rendezvous for one `Worker.process_batch` under "tpu-solve": the
    worker opens a context sized to the dequeued batch, each member eval
    runs inside it (`batch_member`), and the service thread holds the
    next launch open while members that may still submit their FIRST
    bulk solve are running — so a whole `dequeue_batch` result lands in
    ONE joint `tensor/batch_solver.solve_batch` launch instead of
    fragmenting across arrival timing. A member counts as "settled" the
    moment it submits a solve (it is in the queue) or when its run
    returns without one (host path, no-op eval, failure) — either way
    the service never waits on a member that cannot contribute, and the
    wait itself is deadline-bounded (JOINT_WAIT_S) so a wedged member
    degrades the batch to two launches instead of stalling it."""

    __slots__ = ("_lock", "_pending", "expected")

    def __init__(self, expected: int):
        self._lock = threading.Lock()
        self.expected = expected
        self._pending = expected

    def settle(self) -> None:
        with self._lock:
            self._pending -= 1

    def pending(self) -> int:
        with self._lock:
            return self._pending


_batch_tls = threading.local()


def current_batch() -> Optional[BatchContext]:
    return getattr(_batch_tls, "ctx", None)


def open_batch(expected: int) -> BatchContext:
    return BatchContext(expected)


class batch_member:
    """Context manager run by each member eval's thread: binds the
    BatchContext to the thread so the placer's solve call (deep in the
    scheduler stack) finds it, and settles the member on exit if it
    never submitted a joint solve."""

    def __init__(self, ctx: Optional[BatchContext]):
        self._ctx = ctx

    def __enter__(self):
        if self._ctx is not None:
            _batch_tls.ctx = self._ctx
            _batch_tls.settled = False
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            if not getattr(_batch_tls, "settled", True):
                self._ctx.settle()
            _batch_tls.ctx = None
            _batch_tls.settled = True
        return False


def _settle_current_member() -> Optional[BatchContext]:
    """Mark the calling thread's batch member as settled (first joint
    solve submitted); returns the context, or None outside a batch."""
    ctx = current_batch()
    if ctx is not None and not getattr(_batch_tls, "settled", True):
        _batch_tls.settled = True
        ctx.settle()
    return ctx


def ensure_resident(static, feas_base, aff, mesh=None):
    """Device-resident (capacity, mask, affinity) arrays for one
    ClusterStatic, uploaded once and cached in static.device_arrays —
    masks/boosts keyed by host-array identity (the static's mask_cache /
    aff_cache hold the strong refs, so ids can't be recycled). The ONE
    place the cache-key protocol lives; used by the service (single and
    mesh layouts, distinguished by a cache-key tag) and the placer's
    single-eval fused path."""
    import jax

    if mesh is None:
        put_mat = put_row = jax.device_put
        tag = ""
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mat_sh = NamedSharding(mesh, P("nodes", None))
        row_sh = NamedSharding(mesh, P("nodes"))
        put_mat = lambda x: jax.device_put(x, mat_sh)  # noqa: E731
        put_row = lambda x: jax.device_put(x, row_sh)  # noqa: E731
        tag = "sh"
    da = static.device_arrays
    avail = da.get("avail" + tag)
    if avail is None:
        avail = da["avail" + tag] = put_mat(
            static.available.astype(np.float32))
    mkey = ("m" + tag, id(feas_base))
    m = da.get(mkey)
    if m is None:
        m = da[mkey] = put_row(feas_base)
    akey = ("a" + tag, id(aff))
    a = da.get(akey)
    if a is None:
        a = da[akey] = put_row(aff.astype(np.float32))
    return avail, m, a


class _Request:
    __slots__ = ("static", "feas_base", "aff", "ask", "k", "tg_count",
                 "seed", "used_fn", "used_dev_fn", "free_epoch_fn", "future",
                 "token", "joint", "batch_ctx")

    def __init__(self, static, feas_base, aff, ask, k, tg_count, seed,
                 used_fn, joint=False, batch_ctx=None, used_dev_fn=None,
                 free_epoch_fn=None):
        self.static = static
        self.feas_base = feas_base
        self.aff = aff
        self.ask = ask
        self.k = k
        self.tg_count = tg_count
        self.seed = seed
        # called at RESYNC time for a fresh committed-usage base; a base
        # captured at enqueue time goes stale under queue depth and
        # loses usage whose ledger entries already closed (measured
        # in-round: the 2M run's 1% rejection cascade)
        self.used_fn = used_fn
        # optional device-resident base: (mesh) -> committed-usage twin
        # on device (tensor/incremental.py), letting the resync fold
        # ledger entries with one scatter instead of shipping an O(N)
        # host rebuild. None or a failed call falls back to used_fn.
        self.used_dev_fn = used_dev_fn
        # optional () -> count of frees the store's feed has folded
        # (tensor/incremental.py free_epoch_fn): a carry rebuilt at
        # another count holds usage the store has let go of
        self.free_epoch_fn = free_epoch_fn
        self.future = Future()
        self.token = 0
        self.joint = joint          # solve via the batch auction tier
        self.batch_ctx = batch_ctx  # worker-batch rendezvous, or None


class _LedgerEntry:
    """One in-flight solve: where its placements went, awaiting the
    plan outcome."""

    __slots__ = ("static", "idx", "counts", "ask", "born")

    def __init__(self, static, idx, counts, ask, born):
        self.static = static
        self.idx = idx        # (M,) node rows with placements
        self.counts = counts  # (M,) placement counts per row
        self.ask = ask        # (D,) per-placement usage
        self.born = born


class _Inflight:
    """One dispatched-but-unfetched launch: device handles for the
    outputs plus everything the deferred fetch needs to register the
    ledger entries, account stats, and resolve the workers' futures.
    JAX dispatch is async — holding these handles costs nothing until
    jax.device_get, which is the launch's ONLY host sync."""

    __slots__ = ("rs", "static", "counts", "info", "gathers", "rounds",
                 "joint", "sharded", "mesh_devices", "g", "resync", "t0")

    def __init__(self, rs, static, counts, info, gathers, rounds, joint,
                 sharded, mesh_devices, g, resync, t0):
        self.rs = rs
        self.static = static
        self.counts = counts        # (G, N) device handle
        self.info = info            # (6,) device handle (joint) or None
        self.gathers = gathers      # scalar device handle (joint+mesh)
        self.rounds = rounds        # (G,) device handle (greedy+mesh)
        self.joint = joint
        self.sharded = sharded
        self.mesh_devices = mesh_devices
        self.g = g
        self.resync = resync
        self.t0 = t0                # time.time() at dispatch start


class BulkSolverService:
    G_PAD = 16          # evals per launch (padded; k=0 rows are no-ops)
    MAX_K = 32767       # int16 counts ceiling per eval
    RESYNC_SOLVES = 64  # overlay refresh cadence (external usage churn)
    CORRECTIONS = 64    # the kernels' correction slots (always no-ops:
    #                     a rejection resyncs instead, see confirm())
    LEDGER_TTL = 60.0   # s before an unconfirmed solve is presumed dead
    JOINT_WAIT_S = 0.25  # max hold for worker-batch rendezvous members

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # single-entry device state: (static, used_dev, solves_since_sync,
        # the feed's free epoch at the last resync). One entry only — a
        # new node-set version replaces it, and the strong static ref
        # keeps id()-keyed device_arrays coherent.
        self._state = None
        self._token = 0
        self._ledger: Dict[int, _LedgerEntry] = {}
        # set by confirm() on a rejection, cleared by the resync it forces
        self._stale = False
        # mesh scale-out: when the process owns >1 accelerator, the
        # usage carry + capacity/mask rows shard over a node-axis mesh
        # and launches go through solve_bulk_multi_sharded (ONE
        # all-gather per eval — tensor/sharding.py). Resolved lazily on
        # the service thread; _mesh stays None on single-device hosts.
        self._mesh = None
        self._mesh_resolved = False
        self._mesh_solve = None
        self._mesh_solve_joint = None
        # launch telemetry. compiles/retraces split by warmup state:
        # the first launch of a (tier, g_pad, n_pad, d) shape may
        # compile (stats["compiles"]); any cache growth after that is a
        # retrace and raises jit_guard.RetraceError (stats["retraces"]
        # counts them for the agent stats surface before propagating)
        self.stats = {"launches": 0, "solves": 0, "resyncs": 0,
                      "rejections": 0,
                      # resyncs forced by a committed free (the feed's
                      # free epoch moved under a live carry)
                      "stale_frees": 0, "sharded": 0,
                      "joint_launches": 0, "joint_solves": 0,
                      "auction_won": 0, "auction_rounds": 0,
                      "joint_score": 0.0, "greedy_score": 0.0,
                      "compiles": 0, "retraces": 0,
                      # resyncs whose device-twin fold raised and fell
                      # back to the host rebuild (expect 0)
                      "twin_failures": 0,
                      # pipeline telemetry: launches whose fetch was
                      # deferred behind a newer dispatch, and the sharded
                      # launches' collective count. How long the device
                      # was busy is the profiler's to say, not a host
                      # clock's around an asynchronous dispatch
                      "pipelined": 0,
                      "allgathers": 0, "mesh_devices": 0}
        self._warm_shapes: set = set()
        # double buffer: the one dispatched-but-unfetched launch. Only
        # the service thread touches it. While it rides the device, the
        # host resolves the PREVIOUS batch's futures (workers verify +
        # commit their AllocBlocks) and collects/dispatches the next —
        # the solve/apply overlap the c2m rung measures.
        self._inflight: Optional["_Inflight"] = None

    def _resolve_mesh(self, n_pad: int):
        """Largest power-of-two device mesh that divides the padded node
        axis, or None for single-device. NOMAD_TPU_MESH_DEVICES caps the
        mesh (1 forces single-device) so bench sweeps and parity tests
        can pin a size without re-execing under a different XLA device
        count; resolved once per service instance."""
        if not self._mesh_resolved:
            self._mesh_resolved = True
            import os

            import jax

            devs = jax.devices()
            cap = int(os.environ.get("NOMAD_TPU_MESH_DEVICES", "0") or 0)
            if cap > 0:
                devs = devs[:cap]
            if len(devs) > 1:
                from .sharding import (make_solve_batch_sharded,
                                       make_solve_bulk_multi_sharded,
                                       node_mesh)

                n = 1 << (len(devs).bit_length() - 1)
                self._mesh = node_mesh(devs[:n])
                self._mesh_solve = make_solve_bulk_multi_sharded(self._mesh)
                self._mesh_solve_joint = make_solve_batch_sharded(self._mesh)
                with self._lock:
                    self.stats["mesh_devices"] = n
                REGISTRY.set_gauge("nomad.solver.mesh_devices", n)
        if self._mesh is None:
            return None
        n_dev = len(self._mesh.devices.reshape(-1))
        return self._mesh if n_pad % n_dev == 0 else None

    # -- caller side (scheduler worker threads) --

    def solve(self, *, static, feas_base, aff, ask, k, tg_count, seed,
              used_fn, joint=False, used_dev_fn=None, free_epoch_fn=None):
        """Blocking solve of one fresh-placement bulk eval ->
        ((N_pad,) int64 per-node counts in canonical order, token).
        The caller must arrange for confirm(token, rejected_node_ids)
        to run once the plan containing these placements is applied
        (plan.post_apply_hooks). With joint=True ("tpu-solve") the
        request is solved by the global-batch auction kernel together
        with every compatible request in the same launch; a worker-batch
        BatchContext bound to the calling thread rides along so the
        launch waits for the rest of the dequeued batch."""
        req = _Request(static, feas_base, aff,
                       np.asarray(ask, dtype=np.float32), int(k),
                       float(tg_count), np.uint32(seed), used_fn,
                       joint=joint,
                       batch_ctx=current_batch() if joint else None,
                       used_dev_fn=used_dev_fn, free_epoch_fn=free_epoch_fn)
        # put BEFORE ensure: the service thread clears self._thread
        # before its final stop-drain, so a request racing stop() is
        # either caught by that drain (failed, answered) or observes
        # the cleared slot here and starts a fresh thread — ensure
        # first could watch a thread that exits without ever reading
        # the queue, stranding the caller on the future (found by the
        # solve_batch modelcheck scenario)
        self._q.put(req)
        self._ensure_thread()
        if req.batch_ctx is not None:
            # settle AFTER the put: the service may launch without a
            # member whose settle it observed but whose request it
            # didn't — never the reverse
            _settle_current_member()
        # runs on the worker thread inside the eval's trace bind, so
        # the wait (queue + rendezvous + device launch) lands on the
        # eval's own span chain
        with TRACER.span("solver.wait", k=int(k), joint=bool(joint)):
            result = req.future.result()
        return result, req.token

    def confirm(self, token: int, rejected_node_ids) -> None:
        """Plan outcome for one solve: close its ledger entry. Bulk
        solves serialize on one carry and cannot double-book each other,
        so a rejected node holds usage the carry never saw: placements
        made outside this service (the per-placement tier, the host
        path). Taking the phantom back out would not teach the carry
        that — the retry would fill the same node again until its
        attempts ran out and the eval blocked (found by chip_smoke.py: a
        service-job wave, then a bulk job, on one agent) — so a
        rejection marks the carry stale and the next dispatch resyncs."""
        with self._lock:
            if self._ledger.pop(token, None) is None:
                return
            if rejected_node_ids:
                self._stale = True
                self.stats["rejections"] += 1

    def _ensure_thread(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="bulk-solver", daemon=True)
                self._thread.start()

    def stop(self) -> None:
        t = self._thread
        if t is not None and t.is_alive():
            self._q.put(_STOP)
            t.join(timeout=10.0)

    # -- service thread --

    def _retire(self) -> None:
        """Clear the thread slot BEFORE the final stop-drain: any
        solve() that puts after the drain finishes then sees the empty
        slot and starts a fresh thread instead of stranding (solve()
        puts before it checks, so a request the drain missed always
        has its ensure still ahead of it)."""
        with self._lock:
            self._thread = None

    def _run(self) -> None:
        import time as _time

        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                # queue drained: every worker that could feed the next
                # batch may be blocked on the in-flight launch's futures
                # — fetch it (resolving them) BEFORE parking on the
                # queue, or the pipeline deadlocks on an empty queue
                self._fetch_inflight()
                # parked with nothing in flight: the span says whose
                # turn it is while the device idles
                with TRACER.span("solver.idle"):
                    req = self._q.get()
            if req is _STOP:
                self._fetch_inflight()
                self._retire()
                self._drain_failed()
                return
            batch = [req]
            # drain whatever queued while the previous launch ran
            deadline = None
            while len(batch) < self.G_PAD:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    # worker-batch rendezvous: members of an open
                    # BatchContext that haven't settled yet may still
                    # submit — hold the launch (bounded) so the whole
                    # dequeued batch solves jointly
                    if not any(r.batch_ctx is not None
                               and r.batch_ctx.pending() > 0
                               for r in batch):
                        break
                    # spend the hold productively: drain the in-flight
                    # launch now so ITS workers verify/commit while the
                    # rendezvous waits
                    self._fetch_inflight()
                    if deadline is None:
                        deadline = _time.monotonic() + self.JOINT_WAIT_S
                    remain = deadline - _time.monotonic()
                    if remain <= 0:
                        break
                    try:
                        nxt = self._q.get(timeout=min(remain, 0.01))
                    except queue.Empty:
                        continue
                if nxt is _STOP:
                    self._retire()
                    self._flush(batch)
                    self._fetch_inflight()
                    self._drain_failed()
                    return
                batch.append(nxt)
            self._flush(batch)

    def _drain_failed(self) -> None:
        """Fail any request that raced the stop sentinel into the queue —
        its worker is blocked on the future and must not hang."""
        while True:
            try:
                nxt = self._q.get_nowait()
            except queue.Empty:
                return
            if nxt is not _STOP and not nxt.future.done():
                nxt.future.set_exception(
                    RuntimeError("bulk solver service stopped"))

    def _flush(self, batch: List[_Request]) -> None:
        # one launch per distinct (static, tier): mixed statics happen
        # only across a node-set version change, mixed tiers only while
        # an A/B run flips the algorithm — either way the greedy tier's
        # requests must never route through the auction arm, the
        # baseline has to stay pure
        groups = {}
        for r in batch:
            groups.setdefault((id(r.static), r.joint), []).append(r)
        for rs in groups.values():
            try:
                # live, and mirrored into the profiler's trace: the
                # service thread's phases sit above the device's ops
                with TRACER.span("solver.dispatch", device=True,
                                 g=len(rs), joint=bool(rs[0].joint)):
                    inflight = self._dispatch_group(rs)
            except Exception as e:  # propagate to every blocked worker
                # the launch may have consumed (donated) the usage carry
                # before failing — drop the state so the next solve
                # resyncs instead of feeding a deleted buffer back in
                self._state = None
                # the PREVIOUS launch's outputs are independent buffers;
                # drain it so its workers aren't stranded by our failure
                self._fetch_inflight()
                for r in rs:
                    if not r.future.done():
                        r.future.set_exception(e)
                continue
            # double buffer: fetch launch i only now that launch i+1 is
            # queued behind it on the device — i's workers plan-verify
            # and commit while the device solves i+1
            self._fetch_inflight(pipelined=True)
            self._inflight = inflight

    def _fetch_inflight(self, pipelined: bool = False) -> None:
        """Drain the one unfetched launch, if any: register its ledger
        entries, account stats, resolve its workers' futures. Must run
        before anything that rebuilds the carry from the ledger (resync,
        static change, stop) — an unfetched launch has no entries yet,
        so a base built without draining it would silently drop its
        usage from the overlay."""
        inf = self._inflight
        if inf is None:
            return
        self._inflight = None
        try:
            self._fetch(inf, pipelined=pipelined)
        except Exception as e:
            # readback failed: the carry chained off this launch is
            # suspect too — poison it so the next dispatch resyncs
            self._state = None
            for r in inf.rs:
                if not r.future.done():
                    r.future.set_exception(e)

    def _launch_guard(self, fn, shape_key):
        """no_retrace window + warmup accounting for one launch shape:
        the first launch of a shape may compile (stats["compiles"]);
        once a shape is warm any cache growth raises RetraceError and
        any implicit host transfer raises TransferGuard — both are perf
        bugs the tests pin at zero."""
        import contextlib

        from ..analysis import launch_ledger
        from .jit_guard import RetraceError, no_retrace

        @contextlib.contextmanager
        def window():
            warm = shape_key in self._warm_shapes
            win = no_retrace(fn, expect=0 if warm else 2)
            ledger = launch_ledger.window(
                getattr(fn, "__name__", str(fn)), key=shape_key, warm=warm)
            try:
                with ledger, win as counters:
                    yield
            except RetraceError:
                with self._lock:
                    self.stats["retraces"] += 1
                raise
            self._warm_shapes.add(shape_key)
            if counters["compiles"]:
                with self._lock:
                    self.stats["compiles"] += counters["compiles"]
        return window()

    def _resync_base(self, r, static, mesh, d, ledger_entries):
        """Fresh usage carry for a resync: committed usage + open ledger
        entries. Preferred source is the incremental feed's
        device-resident twin (tensor/incremental.py) — the ledger folds
        on-device in one scatter and the O(N) host gather + device_put
        never happens. A feed that cannot serve this static (None) takes
        the exact host path (used_fn + host fold + ship); so does a twin
        whose flush or fold RAISED, but that is counted
        (stats["twin_failures"], nomad.solver.twin_failures) and logged
        with its exception — a scatter that breaks on the device must
        not hide behind the repair."""
        import jax

        from .overlay import INFLIGHT

        if r.used_dev_fn is not None:
            try:
                # the overlay before committed usage, as every usage
                # gather reads them (InflightOverlay.open_entries)
                inflight = INFLIGHT.open_entries()
                dev_base = r.used_dev_fn(mesh)
                if dev_base is not None:
                    return self._fold_base_scatter(dev_base, static, mesh,
                                                   d, ledger_entries,
                                                   inflight)
            except Exception:
                logger.exception("device-twin resync failed; rebuilding "
                                 "the usage carry on the host")
                with self._lock:
                    self.stats["twin_failures"] += 1
                REGISTRY.incr("nomad.solver.twin_failures")
        base = np.asarray(r.used_fn(), dtype=np.float32).copy()
        for idx, counts, ask in ledger_entries:
            base[idx] += counts[:, None].astype(np.float32) * ask[None, :]
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(base, NamedSharding(mesh, P("nodes", None)))
        else:
            return jax.device_put(base)

    def _fold_base_scatter(self, dev_base, static, mesh, d,
                           ledger_entries, inflight):
        """Fold open-ledger + per-eval in-flight (overlay) usage into
        the feed's device base with ONE non-donating scatter launch.
        Non-donating on purpose: the solve kernels donate their usage
        carry (argument 0), and the feed's twin must survive this solve
        — the fold's fresh output array is what enters the donation
        chain. Zero deltas still scatter: the copy IS the protection."""
        import jax

        from .incremental import _scatter_fn
        from .overlay import INFLIGHT

        n_pad = static.n_pad
        rows_list, delta_list = [], []
        for idx, counts, ask in ledger_entries:
            rows_list.append(np.asarray(idx, dtype=np.int32))
            delta_list.append(counts[:, None].astype(np.float32)
                              * np.asarray(ask, np.float32)[None, :])
        tmp = np.zeros((n_pad, d), dtype=np.float32)
        INFLIGHT.fold(tmp[: len(static.nodes)], static.node_index, inflight)
        nz = np.nonzero(np.any(tmp != 0.0, axis=1))[0]
        if nz.size:
            rows_list.append(nz.astype(np.int32))
            delta_list.append(tmp[nz])
        total = sum(len(x) for x in rows_list)
        bucket = 8
        while bucket < total:
            bucket *= 2
        idx = np.zeros(bucket, dtype=np.int32)
        delta = np.zeros((bucket, d), dtype=np.float32)
        pos = 0
        for rr, dd in zip(rows_list, delta_list):
            idx[pos: pos + len(rr)] = rr
            delta[pos: pos + len(rr)] = dd
            pos += len(rr)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .sharding import make_state_scatter_sharded

            n_dev = len(mesh.devices.reshape(-1))
            fn = make_state_scatter_sharded(mesh, donate=False)
            rep = NamedSharding(mesh, P())
            idx = jax.device_put(idx, rep)
            delta = jax.device_put(delta, rep)
            key = ("statefold-sh", n_pad, d, bucket, n_dev)
        else:
            fn = _scatter_fn(donate=False)
            idx, delta = jax.device_put((idx, delta))
            key = ("statefold", n_pad, d, bucket)
        with self._launch_guard(fn, key):
            return fn(dev_base, idx, delta)

    def _device_arrays(self, static, rs, mesh=None):
        """Resident capacity + stacked per-eval mask/affinity arrays
        (node-axis sharded over `mesh` when given); the stacked (G, N)
        combinations are cached by the tuple of the underlying
        host-array ids — repeated batches of the same task-group shapes
        ship nothing."""
        import jax.numpy as jnp

        da = static.device_arrays
        rows_m, rows_a = [], []
        for r in rs:
            avail, m, a = ensure_resident(static, r.feas_base, r.aff,
                                          mesh=mesh)
            rows_m.append((id(r.feas_base), m))
            rows_a.append((id(r.aff), a))
        # joint solves always take the full padded width: padded rows
        # (k=0) exit the kernel loops immediately, and a single-row
        # joint warmup then compiles the SAME shape the production
        # batches run — a g=1 special case would bill a fresh g=G_PAD
        # XLA compile to the first real batch launch
        g_pad = (self.G_PAD if rs[0].joint
                 else 1 if len(rs) == 1 else self.G_PAD)
        while len(rows_m) < g_pad:
            rows_m.append(rows_m[0])
            rows_a.append(rows_a[0])
        # cache the stacked buffers only for UNIFORM batches (every row
        # the same mask/aff — the overwhelmingly common shape): mixed
        # compositions vary by arrival order, and caching each
        # permutation would pin unbounded device memory
        uniform = (all(i == rows_m[0][0] for i, _ in rows_m)
                   and all(i == rows_a[0][0] for i, _ in rows_a))
        skey = ("stack" + ("sh" if mesh is not None else ""), g_pad,
                rows_m[0][0], rows_a[0][0])
        stacked = da.get(skey) if uniform else None
        if stacked is None:
            # on-device stack: no host transfer
            stacked = (jnp.stack([m for _, m in rows_m]),
                       jnp.stack([a for _, a in rows_a]))
            if uniform:
                da[skey] = stacked
        return avail, stacked[0], stacked[1], g_pad

    def _dispatch_group(self, rs: List[_Request]) -> "_Inflight":
        """Build the launch inputs, ship them, and DISPATCH the solve —
        returning device handles without syncing. JAX dispatch is async:
        the returned _Inflight's outputs materialize while the host does
        other work, and the chained usage carry (donated argument 0)
        lets the NEXT dispatch queue behind this one device-side, so
        launch order alone guarantees every solve sees its predecessor's
        usage — never a stale carry — regardless of fetch timing."""
        from .kernels import solve_bulk_multi

        import jax
        import time as _time

        t0 = _time.time()
        static = rs[0].static
        d = static.available.shape[1]
        mesh = self._resolve_mesh(static.n_pad)
        state = self._state
        used_dev, since, synced_at = None, 0, None
        if state is not None and state[0] is static:
            used_dev, since, synced_at = state[1:]
        # read BEFORE a resync takes its base: a free folded in between
        # is then in the base and costs one resync more; read after, it
        # could be in neither
        free_epoch = (rs[0].free_epoch_fn()
                      if rs[0].free_epoch_fn is not None else None)
        freed = used_dev is not None and free_epoch != synced_at

        with self._lock:
            need_resync = (used_dev is None
                           or since >= self.RESYNC_SOLVES
                           or self._stale or freed)
            self._stale = False
            if freed:
                self.stats["stale_frees"] += 1
        if freed:
            REGISTRY.incr("nomad.solver.stale_frees")
        if need_resync:
            # the resync base is committed usage + OPEN ledger entries.
            # A still-unfetched launch has no entries yet — drain it
            # first, or the rebuilt base silently drops its in-flight
            # usage (double-booking burst at the next commit wave)
            self._fetch_inflight()

        now = _time.time()
        with self._lock:
            # unconfirmed solves past the TTL belong to evals that died
            # between solve and submit; presume their placements never
            # committed and stop re-applying them at resync
            dead = [t for t, e in self._ledger.items()
                    if now - e.born > self.LEDGER_TTL]
            for t in dead:
                del self._ledger[t]
            if need_resync:
                # exact rebuild: committed usage + still-in-flight solves
                ledger_entries = [(e.idx, e.counts, e.ask)
                                  for e in self._ledger.values()
                                  if e.static is static]
        if need_resync:
            with TRACER.span("solver.resync", device=True,
                             entries=len(ledger_entries)):
                used_dev = self._resync_base(rs[0], static, mesh, d,
                                             ledger_entries)
            since, synced_at = 0, free_epoch
            with self._lock:
                self.stats["resyncs"] += 1

        cidx = np.zeros(self.CORRECTIONS, dtype=np.int32)
        cdelta = np.zeros((self.CORRECTIONS, d), dtype=np.float32)

        avail, feas, aff, g_pad = self._device_arrays(static, rs, mesh)
        g = len(rs)
        ask = np.zeros((g_pad, d), dtype=np.float32)
        k = np.zeros(g_pad, dtype=np.int32)
        tgc = np.ones(g_pad, dtype=np.float32)
        seeds = np.zeros(g_pad, dtype=np.uint32)
        for i, r in enumerate(rs):
            ask[i] = r.ask
            k[i] = r.k
            tgc[i] = r.tg_count
            seeds[i] = r.seed

        joint = rs[0].joint
        info = gathers = rounds = None
        n_dev = 0 if mesh is None else len(mesh.devices.reshape(-1))
        if mesh is None:
            # explicit shipment of the per-batch host rows so the
            # no_retrace transfer guard can outlaw every IMPLICIT
            # transfer inside the launch window
            ask, k, tgc, seeds, cidx, cdelta = jax.device_put(
                (ask, k, tgc, seeds, cidx, cdelta))
        else:
            # explicit REPLICATED shipment: a bare device_put here would
            # hand the sharded jit uncommitted single-device arrays —
            # the committed-vs-bare cache fork (one graph per layout) —
            # and letting the launch ship them implicitly is exactly
            # what the transfer guard below outlaws on the warm path
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(mesh, P())
            ask, k, seeds, cidx, cdelta = (
                jax.device_put(x, rep)
                for x in (ask, k, seeds, cidx, cdelta))
        if joint and mesh is None:
            from .batch_solver import solve_batch

            with self._launch_guard(solve_batch,
                                    ("joint", g_pad, static.n_pad, d)):
                new_used, counts, info = solve_batch(
                    used_dev, avail, feas, aff, ask, k, tgc, seeds,
                    cidx, cdelta, g=g_pad)
        elif joint:
            with self._launch_guard(
                    self._mesh_solve_joint,
                    ("joint-sh", g_pad, static.n_pad, d, n_dev)):
                new_used, counts, info, gathers = self._mesh_solve_joint(
                    used_dev, avail, feas, aff, ask, k, seeds, cidx,
                    cdelta, g=g_pad)
        elif mesh is not None:
            with self._launch_guard(
                    self._mesh_solve,
                    ("greedy-sh", g_pad, static.n_pad, d, n_dev)):
                new_used, counts, rounds = self._mesh_solve(
                    used_dev, avail, feas, aff, ask, k, seeds, cidx,
                    cdelta, g=g_pad)
        else:
            with self._launch_guard(solve_bulk_multi,
                                    ("greedy", g_pad, static.n_pad, d)):
                new_used, counts = solve_bulk_multi(
                    used_dev, avail, feas, aff, ask, k, tgc, seeds, cidx,
                    cdelta, g=g_pad)
        self._state = (static, new_used, since + g, synced_at)
        if mesh is not None:
            # dispatch-side span: the sharded launch is queued, the host
            # keeps running — the solve/apply overlap window opens here
            TRACER.add_span("solver.shard", t0, _time.time(),
                            g=g, joint=bool(joint), mesh_devices=n_dev)
        RECORDER.record("solver", "launch", g=g, joint=bool(joint),
                        sharded=mesh is not None, resync=need_resync)
        return _Inflight(rs=rs, static=static, counts=counts, info=info,
                         gathers=gathers, rounds=rounds, joint=joint,
                         sharded=mesh is not None, mesh_devices=n_dev,
                         g=g, resync=need_resync, t0=t0)

    def _fetch(self, inf: "_Inflight", pipelined: bool = False) -> None:
        """The launch's ONLY host sync: read the counts (+ info/gather
        stats) back, register ledger entries, account stats, resolve the
        workers' futures. Everything between dispatch and this call is
        host time the device solve ran under."""
        import jax
        import time as _time

        g = inf.g
        t_f0 = _time.time()
        handles = [h for h in (inf.counts, inf.info, inf.gathers,
                               inf.rounds) if h is not None]
        with TRACER.span("solver.fetch", device=True, g=g,
                         pipelined=pipelined):
            got = list(jax.device_get(handles))
        counts_np = got.pop(0)
        info_np = got.pop(0) if inf.info is not None else None
        gathers_np = got.pop(0) if inf.gathers is not None else None
        rounds_np = got.pop(0) if inf.rounds is not None else None
        born = _time.time()
        allg = 0
        if gathers_np is not None:
            allg = int(gathers_np)
        elif rounds_np is not None:
            allg = int(rounds_np[:g].sum())
        # trace-less batch spans (the service thread serves many evals
        # at once); chain gap-attribution picks them up by time overlap,
        # like the raft spans. solver.launch is dispatch start -> fetch
        # end on the clock of the live solver.dispatch / solver.fetch
        TRACER.add_span("solver.launch", inf.t0, born,
                        g=g, joint=bool(inf.joint), sharded=inf.sharded,
                        pipelined=pipelined)
        if inf.sharded:
            TRACER.add_span("solver.allgather", t_f0, born, gathers=allg,
                            per_eval=allg / max(g, 1))
        with self._lock:
            # counters share self._lock with the ledger: solve()/confirm()
            # mutate stats from API threads under the same lock
            self.stats["launches"] += 1
            self.stats["solves"] += g
            self.stats["allgathers"] += allg
            if pipelined:
                self.stats["pipelined"] += 1
            if inf.sharded:
                self.stats["sharded"] += 1
            if info_np is not None:
                self.stats["joint_launches"] += 1
                self.stats["joint_solves"] += g
                self.stats["auction_won"] += int(info_np[5] > 0.5)
                self.stats["auction_rounds"] += int(info_np[4])
                self.stats["joint_score"] += float(
                    info_np[0] if info_np[5] > 0.5 else info_np[1])
                self.stats["greedy_score"] += float(info_np[1])
            for i, r in enumerate(inf.rs):
                row = counts_np[i]
                idx = np.nonzero(row)[0]
                self._token += 1
                r.token = self._token
                self._ledger[r.token] = _LedgerEntry(
                    inf.static, idx, row[idx].astype(np.int64), r.ask,
                    born)
        # mirror the service stats into the Registry so /v1/metrics and
        # bench dumps carry them without reaching into the singleton
        # (REGISTRY is a leaf lock — taken after self._lock is dropped)
        REGISTRY.incr("nomad.solver.launches")
        REGISTRY.incr("nomad.solver.solves", g)
        if allg:
            REGISTRY.incr("nomad.solver.allgathers", allg)
        if info_np is not None:
            REGISTRY.incr("nomad.solver.auction_won",
                          int(info_np[5] > 0.5))
            REGISTRY.incr("nomad.solver.auction_rounds", int(info_np[4]))
            REGISTRY.incr("nomad.solver.joint_score", float(
                info_np[0] if info_np[5] > 0.5 else info_np[1]))
            REGISTRY.incr("nomad.solver.greedy_score", float(info_np[1]))
        for i, r in enumerate(inf.rs):
            r.future.set_result(counts_np[i].astype(np.int64))


_service: Optional[BulkSolverService] = None
_service_lock = threading.Lock()


def get_service() -> BulkSolverService:
    global _service
    if _service is None:
        with _service_lock:
            if _service is None:
                _service = BulkSolverService()
    return _service
