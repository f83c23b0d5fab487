"""TPUPlacer: batched placement behind SchedulerAlgorithm="tpu-binpack"
(the new algorithm value plugging into the reference's enum,
nomad/structs/operator.go:199-255).

Lowering strategy per evaluation:
  1. one ClusterTensors build (nodes + proposed usage),
  2. per task group: host-precompiled feasibility/affinity/spread arrays,
     device/core count columns, and distinct_property cap tables,
  3. one solve per task group: the host scorer per placement for a
     group of at most HOST_CUTOVER; the count solve through the solver
     service (tensor/solver.py, one AllocBlock) for the BestFit bulk
     request the reconciler emits (scheduler/reconcile.py
     BULK_PLACE_MIN); else one jitted solve_task_group scan placing all
     of the group's requests with full cross-placement visibility,
  4. commits mapped back through the scheduler's commit callback so the
     plan object and ctx.proposed_allocs stay authoritative. Exact port
     numbers, device instance ids, and core ids are assigned host-side
     per chosen node after the solve (counts were fit on-device): the
     ports and the device instances while the solve lock is still
     held, so that they go into the in-flight overlay with the usage
     they belong to and racing evaluations never pick the same number
     or instance (structs/network.py, scheduler/devices.py), the core
     ids in the row loop. A group of fresh placements that needs no
     core ids is committed as one AllocBlock, from the scan as from the
     count solve, its ports and instances in the block's columns.

Preemption: what the solve left unplaced, with preemption enabled, is
one kernels.preempt_solve launch (node and victims, each row revalidated
on the host); flagged rows take the host NodeScorer preemption path
(reference rank.go:205-587's preemption fallback arm). A request
whose post-solve id assignment fails (NUMA "require" mispredicted by
count-fit, overlapping device asks) falls back to the host selector for
that request alone.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..obs import TRACER
from ..structs import Job, Node, enums
from ..scheduler.context import EvalContext
from ..scheduler.rank import NodeScorer, RankedNode, select_best_node
from ..scheduler.reconcile import PlacementRequest
from .cluster import ClusterTensors, build_task_group_tensors, _pad_pow2


def _binpack_fitness_np(available: np.ndarray, used: np.ndarray) -> np.ndarray:
    """Vectorized BestFit-v3 fit score (reference funcs.go:236
    ScoreFitBinPack), shared by the preemption solve mirror and the bulk
    trajectory mean. Thin wrapper over kernels._fit_scores_xp — the one
    formula the device kernels, the batch solver, and this host oracle
    all evaluate (parity pinned by test_batch_solver.py)."""
    from .kernels import fit_scores_np
    return fit_scores_np(available, used, spread_alg=False)


def _preempt_solve_host(available, used, ask, feasible, net_prio, active,
                        v_prio, v_vec, v_elig, v_flag):
    """Numpy mirror of kernels.preempt_solve — same node ordering AND the
    same priority-ascending victim-prefix rule, same op order, so the
    small-shape path and the parity tests pin the kernel bit-exact
    (victims, order, post-eviction usage). Returns (picks, victims,
    flagged, scores) with the kernel's shapes."""
    pscore = 1.0 / (1.0 + np.exp(0.0048 * (net_prio - 2048.0)))
    used = np.asarray(used, dtype=np.float64).copy()
    v_vec = np.asarray(v_vec, dtype=np.float64)
    elig = np.asarray(v_elig, dtype=bool)
    ev = (v_vec * elig[:, :, None]).sum(axis=1)
    taken = np.zeros(elig.shape, dtype=bool)
    kq, vq = active.shape[0], elig.shape[1]
    picks = np.full(kq, -1, dtype=np.int32)
    victims = np.zeros((kq, vq), dtype=bool)
    flagged = np.zeros(kq, dtype=bool)
    neg = -1.0e30
    scores = np.full(kq, neg)
    for i in range(kq):
        if not active[i]:
            continue
        new_used = used + ask[None, :]
        deficit = np.maximum(new_used - available, 0.0)
        can = feasible & (deficit <= ev).all(axis=1)
        if not can.any():
            continue
        needs_evict = (deficit > 0.0).any(axis=1)
        fitness = _binpack_fitness_np(available,
                                      np.minimum(new_used, available))
        score = np.where(
            can,
            (fitness + np.where(needs_evict, pscore, 0.0))
            / (1.0 + needs_evict.astype(float)),
            neg)
        best = int(np.argmax(score))
        if score[best] <= neg:
            continue
        row = elig[best] & ~taken[best]
        vecs = v_vec[best] * row[:, None]
        cum_before = np.cumsum(vecs, axis=0) - vecs
        def_b = deficit[best]
        sel = (row & bool(needs_evict[best])
               & ((def_b[None, :] > 0.0)
                  & (cum_before < def_b[None, :])).any(axis=1))
        evicted = (v_vec[best] * sel[:, None]).sum(axis=0)
        picks[i] = best
        victims[i] = sel
        flagged[i] = bool((sel & v_flag[best]).any())
        scores[i] = score[best]
        used[best] = np.maximum(used[best] + ask - evicted, 0.0)
        ev[best] = np.maximum(ev[best] - evicted, 0.0)
        taken[best] |= sel
    return picks, victims, flagged, scores


# Preemption-path counters: kernel_preempted = placements whose victims
# came straight from the preempt_solve column prefix; host_preempted =
# rows re-routed through the exact host scanner (flagged port/device
# holders, exact-resource groups, or a revalidation miss);
# victim_parity_checked = kernel rows revalidated host-side via
# allocs_fit before commit (every kernel row takes this check, so
# kernel_preempted counts only validated successes). Mirrored into the
# Registry as nomad.preempt.* for the obs plane; read via
# preempt_stats() (chip_smoke leg D, chaos solve-smoke).
# The system path's (TPUPlacer.place_system): preempting_nodes = nodes
# whose placement preempted, victims = allocations it preempted,
# host_fallback_nodes = nodes it handed to the exact host scorer
# (a victim holding ports or devices, or max_parallel penalties that
# couple the node to an earlier one in the same launch); column_nodes =
# nodes whose victim columns were built, kept_column_nodes = those of
# them read from the store's kept allocation columns (not walked).
PREEMPT_STATS = {"kernel_preempted": 0, "host_preempted": 0,
                 "victim_parity_checked": 0, "preempting_nodes": 0,
                 "victims": 0, "host_fallback_nodes": 0,
                 "column_nodes": 0, "kept_column_nodes": 0}
_PREEMPT_STATS_LOCK = __import__("threading").Lock()
# shapes (n_pad, k_pad, v_pad, d) already compiled: later launches of the
# same shape run under a jit_guard no_retrace window (retrace there is a
# bug, not a warmup)
_PREEMPT_WARM: set = set()
# same discipline for the other placer-driven launch sites: the
# per-placement scan and the system placement
_FUSED_WARM: set = set()
_SYSTEM_WARM: set = set()


def _warm_launch(fn, shape_key, warm: set):
    """Shape-keyed launch window around one kernel launch; the
    implementation now lives in :func:`solver.warm_launch` (shared with
    the solver service and the incremental-state scatter), kept here as
    an alias so the placer's launch sites and tests keep their name."""
    from .solver import warm_launch

    return warm_launch(fn, shape_key, warm)


def preempt_stats() -> Dict[str, int]:
    """Snapshot of the preemption-path counters (thread-safe copy)."""
    with _PREEMPT_STATS_LOCK:
        return dict(PREEMPT_STATS)


def _count_preempt(**deltas: int) -> None:
    from ..core.metrics import REGISTRY

    with _PREEMPT_STATS_LOCK:
        for key, n in deltas.items():
            PREEMPT_STATS[key] += n
    for key, n in deltas.items():
        # at 0 too: a reader of the share finds its counters
        REGISTRY.incr(f"nomad.preempt.{key}", n)


# One solve at a time across racing workers' PER-EVAL kernel path (the
# device serializes launches regardless); see the critical-section note
# in place(). The bulk path has its own serializer (the solver service).
_PER_EVAL_SOLVE_LOCK = __import__("threading").Lock()

# How many evaluations may be between staging their solve and the end of
# their hold of that lock: the holder, and two staged behind it so that
# the lock never waits for one. The lock serializes this tier whatever
# the number of workers, and staging is interpreted work: every further
# evaluation that stages alongside only takes the interpreter from the
# holder at each of its releases (the gather's copy, device_put, the
# launch, the wake from block_until_ready, device_get), and from the
# commit path behind it. With 24 workers staging at once the same
# backlog drained at 7,000 to 14,600 allocations a second run by run
# (PERF.md section 6, PR 29). A constant, not an option: `--workers`
# says how many evaluations may be in flight, this how many of them
# queue for one lock with their hands full.
_SOLVE_ADMIT = __import__("threading").BoundedSemaphore(3)


class TPUPlacer:
    """Placer implementation: dense-tensor batch solve on the device."""

    def __init__(self, algorithm: str = enums.SCHED_ALG_BINPACK):
        from .backend import require_tpu

        # every tpu-* placement is built here, also after an operator
        # flips the algorithm on a running agent: no silent CPU arm
        require_tpu()
        # fit formula to use on the device; "tpu-binpack" keeps BestFit
        self.algorithm = algorithm

    def place(
        self,
        ctx: EvalContext,
        job: Job,
        requests: Sequence[PlacementRequest],
        nodes: Sequence[Node],
        commit,
        *,
        batch: bool = False,
        preemption_enabled: bool = False,
        attempt: int = 0,
    ) -> None:
        if not nodes:
            from ..scheduler.reconcile import BulkPlacementRequest

            for req in requests:
                m = ctx.new_metrics()
                m.nodes_in_pool = 0
                if isinstance(req, BulkPlacementRequest):
                    fail_bulk = getattr(commit, "fail_bulk", None)
                    if fail_bulk is not None:
                        fail_bulk(req.task_group, req.count)
                        continue
                    for r in req.expand():
                        commit(r, None)
                    continue
                commit(req, None)
            return

        # Per-eval tie-break permutation, same seed discipline as the
        # host path's node shuffle (reference scheduler/util.go:167
        # shuffleNodes): scores are order-invariant, but the kernel's
        # argmax tie-breaks by priority order — without it every
        # concurrently-racing worker picks the same winners among
        # equal-scoring nodes and the plan applier rejects all but one
        # (optimistic-concurrency livelock). The permutation rides INTO
        # the kernel so the host-side node order stays canonical and the
        # per-node arrays stay cacheable across evals (ClusterStatic).
        with TRACER.span("worker.tensor_build", n=len(nodes)):
            cluster = ClusterTensors.build(ctx, nodes)
        nodes = cluster.nodes
        # crc32, not hash(): the seed must be deterministic ACROSS
        # processes (leader failover replaying an eval must explore the
        # same permutation), and hash() is salted per process
        seed = zlib.crc32(f"{ctx.eval_id}:{attempt}".encode())
        tie_perm = np.random.default_rng(seed).permutation(
            cluster.n_pad).astype(np.int32)

        # group requests per task group, preserving intra-group order
        groups: Dict[str, List[PlacementRequest]] = {}
        order: List[str] = []
        for req in requests:
            name = req.task_group.name
            if name not in groups:
                groups[name] = []
                order.append(name)
            groups[name].append(req)

        for gi, name in enumerate(order):
            reqs = groups[name]
            tg = reqs[0].task_group
            if gi > 0:  # build() already computed usage for the first group
                cluster.refresh_usage(ctx)

            from ..scheduler.reconcile import BulkPlacementRequest

            if len(reqs) == 1 and isinstance(reqs[0], BulkPlacementRequest):
                # columnar fast path: K fresh placements as ONE request
                # committed as ONE AllocBlock (the reconciler only emits
                # this shape when nothing per-alloc is pending)
                bulk = reqs[0]
                tgt = build_task_group_tensors(ctx, job, tg, cluster,
                                               algorithm=self.algorithm)
                takes_blocks = getattr(commit, "commit_block", None) is not None
                if takes_blocks and self._bulk_shape_ok(ctx, tg, tgt,
                                                        bulk.count):
                    with TRACER.span("worker.solve_bulk", k=bulk.count):
                        self._place_bulk_columnar(
                            ctx, job, tg, bulk, cluster, tgt, commit, seed,
                            sched_batch=batch,
                            preemption_enabled=preemption_enabled,
                            attempt=attempt)
                    continue
                if (takes_blocks and bulk.count > self.HOST_CUTOVER
                        and not self._wants_ids(ctx, tg)):
                    # spread / distinct_hosts / distinct_property /
                    # ports / devices / a CSI mask / K over the service's
                    # ceiling rule out the count solve, not the block:
                    # the scan's K placements are committed as ONE
                    # AllocBlock too, with the ports and instances
                    # chosen under the hold
                    self._place_scan_columnar(
                        ctx, job, tg, bulk, cluster, tgt, commit, tie_perm,
                        sched_batch=batch,
                        preemption_enabled=preemption_enabled,
                        attempt=attempt)
                    continue
                # cores are assigned per placement on its chosen node:
                # expand and fall through (reusing the tensors just
                # built)
                reqs = bulk.expand()
                prebuilt_tgt = tgt
            else:
                prebuilt_tgt = None

            if len(reqs) <= self.HOST_CUTOVER:
                # tiny groups (mostly partial-commit remainders): the
                # host oracle scores the same nodes per placement — same
                # math, parity-tested — without a launch's fixed cost.
                # HOST_CUTOVER selects the arm; its value is not
                # measured on the current chip (ROADMAP D3)
                from ..core.metrics import REGISTRY

                REGISTRY.incr("nomad.placer.host_cutover_groups")
                held: Dict[str, list] = {}
                held_devices: Dict[str, dict] = {}
                for req in reqs:
                    option = self._host_one(ctx, job, tg, nodes, req,
                                            batch, preemption_enabled,
                                            attempt)
                    commit(req, option)
                    _hold(option, held, held_devices)
                if (held or held_devices) and ctx.plan is not None:
                    # the scorer read the overlay's ports and instances
                    # (ctx.port_index, ctx.device_index); what it chose
                    # goes there in turn, for the evaluations racing
                    # behind it
                    from .overlay import INFLIGHT

                    INFLIGHT.register(cluster, (), None, ctx.plan, held,
                                      held_devices)
                continue

            tgt = (prebuilt_tgt if prebuilt_tgt is not None
                   else build_task_group_tensors(ctx, job, tg, cluster,
                                                 algorithm=self.algorithm))

            penalty_idx = np.full(_pad_pow2(len(reqs), floor=1), -1,
                                  dtype=np.int32)
            for i, req in enumerate(reqs):
                if req.ignore_node:
                    penalty_idx[i] = cluster.node_index.get(req.ignore_node, -1)
            choices, founds, scores, ports, devices = self._scan_group(
                ctx, tg, cluster, tgt, len(reqs), penalty_idx, tie_perm)
            with TRACER.span("placer.rows", k=len(reqs)):
                self._place_rows(ctx, job, tg, reqs, cluster, tgt, commit,
                                 choices, founds, scores, ports, devices,
                                 batch=batch,
                                 preemption_enabled=preemption_enabled,
                                 attempt=attempt)

    def _place_rows(self, ctx, job, tg, reqs, cluster, tgt, commit,
                    choices, founds, scores, ports, devices, *,
                    batch: bool, preemption_enabled: bool,
                    attempt: int) -> None:
        """The scan's placements handed over one row each: a RankedNode,
        an AllocMetric and (in commit) an Allocation a placement. Exact
        port numbers and device instances were chosen under the solve
        lock (`ports`: _assign_ports, `devices`: _assign_devices); core
        ids are assigned here, per chosen node (the kernel only
        fit-checked the counts), with per-node sets that carry the
        assignments across this group's placements so they don't
        double-book. A node that ran short of instances sends its
        request to the host selector alone."""
        nodes = cluster.nodes
        ask_res = ctx.tg_resources(tg)
        wants_cores = bool(ask_res.cores)
        numa_pol = "none"
        if wants_cores:
            from ..scheduler.devices import combined_numa_affinity

            numa_pol = combined_numa_affinity(tg)
        core_used: Dict[int, set] = {}
        # what the host selector chose for a device-asking group's
        # requests: registered with the overlay below, as the host
        # cut-over registers its own
        held_ports: Dict[str, list] = {}
        held_devices: Dict[str, dict] = {}

        n_feasible = int(tgt.feasible[: len(nodes)].sum())
        preempt_queue: List[PlacementRequest] = []
        for i, req in enumerate(reqs):
            metrics = ctx.new_metrics()
            metrics.nodes_in_pool = len(nodes)
            metrics.nodes_evaluated = len(nodes)
            if founds[i]:
                ni = int(choices[i])
                node = nodes[ni]
                option = RankedNode(node=node)
                option.final_score = float(scores[i])
                option.score_meta["normalized-score"] = option.final_score
                metrics.scores[f"{node.id}.normalized-score"] = option.final_score
                if ports is not None:
                    # this node's next share of what the hold chose
                    mine = ports[ni].pop() if ports[ni] else None
                    if mine is None:
                        metrics.exhaust_node("ports")
                        commit(req, None)
                        continue
                    option.allocated_ports = mine
                ok = True
                if devices is not None:
                    got = devices[ni].pop() if devices[ni] else None
                    ok = got is not None
                    option.allocated_devices = got or {}
                if ok and wants_cores:
                    ok = self._assign_cores(ctx, ask_res, numa_pol, ni,
                                            node, option, core_used)
                if not ok:
                    # count-fit admitted a node the exact assignment
                    # can't satisfy (instances taken by a racing
                    # evaluation / NUMA require / overlapping asks): host
                    # selector for this request alone (its ports and
                    # instances from ctx.port_index / ctx.device_index,
                    # which hold what the hold chose for the rest)
                    option = self._host_one(ctx, job, tg, nodes, req,
                                            batch, preemption_enabled,
                                            attempt)
                    commit(req, option)
                    if option is not None:
                        # the fallback assigned ids on its own node;
                        # drop that node's cores so later kernel
                        # placements rebuild them from the committed
                        # plan instead of double-booking
                        self._invalidate_node(cluster, option.node.id,
                                              core_used)
                        if devices is not None:
                            _hold(option, held_ports, held_devices)
                    continue
                commit(req, option)
                continue
            if preemption_enabled:
                preempt_queue.append(req)
                continue
            self._attribute_failure(ctx, metrics, len(nodes), n_feasible)
            commit(req, None)
        if (held_ports or held_devices) and ctx.plan is not None:
            from .overlay import INFLIGHT

            INFLIGHT.register(cluster, (), None, ctx.plan, held_ports,
                              held_devices)
        if preempt_queue:
            self._preempt_batch(
                ctx, job, tg, preempt_queue, cluster, tgt, commit,
                sched_batch=batch, attempt=attempt,
                n_feasible=n_feasible,
                invalidate=lambda nid: self._invalidate_node(
                    cluster, nid, core_used))

    # Path-selection constants. Each was set from a launch cost seen in
    # an earlier environment; value not measured on the current chip
    # (ROADMAP D3). When a group becomes a count solve is the
    # reconciler's call (scheduler/reconcile.py BULK_PLACE_MIN).
    HOST_CUTOVER = 16  # selects host oracle (at/below) vs device launch
    # selects preempt_solve on the device (n_pad * k_pad at/above) vs
    # its numpy mirror
    PREEMPT_DEVICE_MIN = 1 << 18

    @staticmethod
    def _wants_ports(ctx, tg) -> bool:
        ask_res = ctx.tg_resources(tg)
        return bool(ask_res.reserved_port_asks()
                    or ask_res.dynamic_port_count())

    @staticmethod
    def _wants_ids(ctx, tg) -> bool:
        """Whether a placement of this group carries ids assigned on its
        chosen node after the solve: core ids, the row loop's
        wants_cores. Port numbers and device instances are chosen under
        the hold for every touched node at once (_assign_ports,
        _assign_devices), so asking them keeps no group out of a
        block."""
        return bool(ctx.tg_resources(tg).cores)

    def _scan_group(self, ctx, tg, cluster, tgt, k: int, penalty_idx,
                    tie_perm, columnar: bool = False):
        """One group's per-placement scan of k placements: admitted,
        staged outside _PER_EVAL_SOLVE_LOCK, solved under it ->
        (choices, founds, scores, ports, devices): the first three of the
        padded length, then _assign_ports' result for a group that asks
        ports and _assign_devices' for one that asks devices, else None.
        `penalty_idx` is the padded (k_pad,) reschedule-penalty row;
        `columnar` says the caller hands the result over as one
        AllocBlock (counted beside the staged solves: the share of
        groups that take that arm)."""
        from ..core.metrics import REGISTRY

        REGISTRY.incr("nomad.placer.columnar_scan_groups", int(columnar))
        k_pad = len(penalty_idx)
        active = np.zeros(k_pad, dtype=bool)
        active[:k] = True
        # Everything the solve reads that no racing evaluation can
        # change is packed and put on the device here, while the
        # worker would otherwise only wait for the lock: the hold
        # below is left with what depends on other evaluations'
        # placements (the usage gather) and the launch itself.
        with TRACER.span("placer.admit"):
            _SOLVE_ADMIT.acquire()
        try:
            staged = self._stage_statics(tgt, cluster, penalty_idx,
                                         active, tie_perm)
            return self._solve_staged(ctx, tg, cluster, k, k_pad, staged)
        finally:
            _SOLVE_ADMIT.release()

    def _place_scan_columnar(self, ctx, job, tg, bulk, cluster, tgt,
                             commit, tie_perm, *, sched_batch: bool,
                             preemption_enabled: bool, attempt: int) -> None:
        """The per-placement scan's K fresh placements handed over as
        one AllocBlock: the launch, its choices and so the placements
        are the row loop's, node for node; what follows the scan is
        numpy over K and a list over the touched nodes, not a
        RankedNode, an AllocMetric and an Allocation a placement. Block
        positions are the found placements in stable order of their
        chosen node, each with its own name index and score, and with
        the ports and device instances the hold chose for a group that
        asks them: a node's positions take its port lists and its
        instance assignments in the order the row loop pops them, so
        the block is the row loop's placements port for port and
        instance for instance. A node that ran short of instances sends
        its surplus placements to the host selector, each on its own, as
        the row loop does (_host_surplus)."""
        k = bulk.count
        # a bulk request has no ignore_node: no reschedule penalty
        penalty_idx = np.full(_pad_pow2(k, floor=1), -1, dtype=np.int32)
        choices, founds, scores, ports, devices = self._scan_group(
            ctx, tg, cluster, tgt, k, penalty_idx, tie_perm, columnar=True)

        # the block's assembly, its commit and the tail: span
        # placer.block (arg `nodes`, the nodes the block touches)
        with TRACER.span("placer.block") as span:
            nodes = cluster.nodes
            metrics = ctx.new_metrics()
            metrics.nodes_in_pool = len(nodes)
            metrics.nodes_evaluated = len(nodes)
            name_indices = np.asarray(bulk.name_indices, dtype=np.int64)
            found = np.flatnonzero(founds[:k])
            rows, surplus = (), found[:0]
            if len(found):
                # np.unique's sorted rows are the stable order's node runs
                found = found[np.argsort(choices[found], kind="stable")]
                rows, counts = np.unique(choices[found], return_counts=True)
                block_ports = block_devices = None
                if ports is not None:
                    keep, block_ports = self._block_ports(rows, counts,
                                                          ports)
                    if not keep.all():
                        # a node that ran out of ports: its surplus
                        # placements fail, as the row loop's did
                        metrics.exhaust_node("ports")
                        commit.fail_bulk(tg, int((~keep).sum()))
                        found = found[keep]
                        rows, counts = np.unique(choices[found],
                                                 return_counts=True)
                if devices is not None:
                    keep, block_devices = self._block_devices(rows, counts,
                                                              devices)
                    if not keep.all():
                        # a node that ran short of instances: its
                        # surplus goes to the host selector, in scan order
                        surplus = np.sort(found[~keep])
                        found = found[keep]
                        if block_ports is not None:
                            block_ports.values = block_ports.values[keep]
                        rows, counts = np.unique(choices[found],
                                                 return_counts=True)
                rows = rows.tolist()
            if len(found):
                placed_scores = scores[found]
                commit.commit_block(
                    tg,
                    [nodes[ni].id for ni in rows],
                    [nodes[ni].name for ni in rows],
                    counts.astype(np.int64),
                    name_indices[found],
                    float(placed_scores.mean()),
                    scores=placed_scores,
                    nodes_evaluated=len(nodes),
                    nodes_in_pool=len(nodes),
                    ports=block_ports,
                    devices=block_devices)
            span.set(nodes=len(rows))

            if len(surplus):
                self._host_surplus(ctx, job, tg, bulk.job_id,
                                   name_indices[surplus], cluster, commit,
                                   batch=sched_batch,
                                   preemption_enabled=preemption_enabled,
                                   attempt=attempt)
                # the tail's failure, if any, is this metric's
                ctx.metrics = metrics
            self._bulk_tail(ctx, job, tg, bulk.job_id,
                            name_indices[~founds[:k]], metrics, cluster,
                            tgt, commit, sched_batch=sched_batch,
                            preemption_enabled=preemption_enabled,
                            attempt=attempt)

    def _host_surplus(self, ctx, job, tg, job_id, surplus_indices, cluster,
                      commit, *, batch: bool, preemption_enabled: bool,
                      attempt: int) -> None:
        """The placements a device block left out (`surplus_indices`:
        their name indices, in scan order), each to the host selector on
        its own, as _place_rows sends a request whose node ran short of
        instances; what it chooses is registered with the in-flight
        overlay, as there. Counter nomad.placer.device_block_surplus."""
        from ..core.metrics import REGISTRY
        from ..scheduler.reconcile import BulkPlacementRequest
        from .overlay import INFLIGHT

        REGISTRY.incr("nomad.placer.device_block_surplus",
                      len(surplus_indices))
        held_ports: Dict[str, list] = {}
        held_devices: Dict[str, dict] = {}
        for req in BulkPlacementRequest(task_group=tg, job_id=job_id,
                                        name_indices=surplus_indices
                                        ).expand():
            option = self._host_one(ctx, job, tg, cluster.nodes, req, batch,
                                    preemption_enabled, attempt)
            commit(req, option)
            _hold(option, held_ports, held_devices)
        if (held_ports or held_devices) and ctx.plan is not None:
            INFLIGHT.register(cluster, (), None, ctx.plan, held_ports,
                              held_devices)

    @staticmethod
    def _keep_first(counts, held) -> np.ndarray:
        """Over positions in node runs of `counts`, whether each is among
        the first `held[i]` of its run i: those a node's ids serve."""
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        return np.arange(len(starts)) - starts < np.repeat(held, counts)

    @classmethod
    def _block_ports(cls, rows, counts, ports):
        """Lay _assign_ports' lists (`ports`: {row: lists, the first
        chosen last}) over a block's positions (node runs of `rows` x
        `counts`): a node's first placement takes its first list. A node
        that ran out holds fewer lists than placements; its last
        placements in scan order leave the block. -> (the mask of the
        positions that keep their ports, their BlockPorts)."""
        from ..structs.alloc import BlockPorts

        held = np.fromiter((len(ports[ni]) for ni in rows.tolist()),
                           np.int64, len(rows))
        lists = [got for ni, c in zip(rows.tolist(),
                                      np.minimum(counts, held).tolist())
                 for got in ports[ni][:-c - 1:-1]]
        labels = [p.label for p in lists[0]] if lists else []
        values = np.array([[p.value for p in got] for got in lists],
                          np.int32).reshape(-1, len(labels))
        return (cls._keep_first(counts, held),
                BlockPorts(labels, values))

    @classmethod
    def _block_devices(cls, rows, counts, devices):
        """Lay _assign_devices' choice (`devices`: {row: assignments,
        the first chosen last}) over a block's positions as
        _block_ports lays ports: a node's first placement takes its
        first assignment, and the last placements of a node that ran
        short leave the block. -> (the mask of the positions that keep
        their instances, their BlockDevices)."""
        from ..structs.alloc import BlockDevices

        held = np.fromiter((len(devices[ni]) for ni in rows.tolist()),
                           np.int64, len(rows))
        return (cls._keep_first(counts, held), BlockDevices.of(
            got for ni, c in zip(rows.tolist(),
                                 np.minimum(counts, held).tolist())
            for got in devices[ni][:-c - 1:-1]))

    def _bulk_tail(self, ctx, job, tg, job_id, tail_indices, metrics,
                   cluster, tgt, commit, *, sched_batch: bool,
                   preemption_enabled: bool, attempt: int) -> None:
        """What a bulk request's solve left unplaced (`tail_indices`:
        their name indices): one coalesced failure, or, with preemption
        enabled, the per-request preemption machinery for the remainder
        ALONE, expanded here."""
        if not len(tail_indices):
            return
        nodes = cluster.nodes
        n_feasible = int(tgt.feasible[: len(nodes)].sum())
        if preemption_enabled:
            from ..scheduler.reconcile import BulkPlacementRequest

            remainder = BulkPlacementRequest(
                task_group=tg, job_id=job_id,
                name_indices=tail_indices).expand()
            self._preempt_batch(ctx, job, tg, remainder, cluster, tgt,
                                commit, sched_batch=sched_batch,
                                attempt=attempt, n_feasible=n_feasible)
            return
        self._attribute_failure(ctx, metrics, len(nodes), n_feasible)
        commit.fail_bulk(tg, len(tail_indices))

    def _stage_statics(self, tgt, cluster, penalty_idx, active, tie_perm):
        """Put on the device, before _PER_EVAL_SOLVE_LOCK is taken,
        everything the fused solve reads but usage. What the node set
        fixes (capacity, the cached feasibility mask, the affinity
        vector, the attributes' interned rows, the zero placement counts
        of a job with none) is resident on the cluster's static and
        uploaded only by the first solve that reads it; what only this
        evaluation fixes (the tie permutation, the step rows, the
        spread and distinct_property tables, the scalars, device / core
        columns) is one buffer (kernels.pack_eval_args), and a mask or
        placement counts of its own (a reserved-port or CSI mask, a job
        with allocations) ride with it in the one device_put. Which is
        which follows from the group's tensors, nothing else.
        -> (device arguments, layout, usage buffer, extra usage columns
        or None, the device asks' matched groups or None). The usage
        buffer is this group's (n_pad, D) f32 gather target, allocated
        here so that the hold does not; the extra columns (device / core
        counts, this evaluation's own view) extend it under placer.pack,
        a device ask's with the in-flight overlay's instances added
        through its matched groups (cluster.fold_inflight_devices)."""
        import jax

        from ..core.metrics import REGISTRY
        from .kernels import pack_eval_args, scan_steps
        from .solver import (ensure_resident, resident_rows,
                             resident_zero_counts)

        static = cluster.static
        with TRACER.span("placer.stage", device=True) as span:
            uploads: list = []
            avail, feasible, aff = ensure_resident(
                static, tgt.feas_base, tgt.affinity_boost, uploads=uploads)
            spread = resident_rows(static, tgt.spread_rows, uploads)
            dp = resident_rows(static, tgt.dp_rows, uploads)
            placed = None
            if (tgt.placed_tg is static.placed_zero
                    and tgt.placed_job is static.placed_zero):
                placed = resident_zero_counts(static, uploads)
            extra_used = extra_cap = dev_aff = None
            ask = tgt.ask
            if tgt.extra_ask is not None and len(tgt.extra_ask):
                # device/core count columns extend the dense dims
                extra_cap, dev_aff = tgt.extra_cap, tgt.dev_affinity
                ask = np.concatenate([ask, tgt.extra_ask])
                extra_used = np.asarray(tgt.extra_used, np.float32)
            ev, layout = pack_eval_args(
                tie_perm, penalty_idx, active, tgt.spread_counts,
                tgt.spread_desired, tgt.spread_has_targets,
                tgt.spread_weight, tgt.dp_counts, tgt.dp_limit,
                -1.0, tgt.tg_count, tgt.dh_job, tgt.dh_tg, tgt.spread_alg,
                ask, extra_cap=extra_cap, dev_affinity=dev_aff)
            own = [ev]
            if feasible is None:
                own.append(tgt.feasible)
            if placed is None:
                own.append(np.stack([tgt.placed_tg, tgt.placed_job]))
            span.set(bytes=sum(a.nbytes for a in own))
            shipped = jax.device_put(own)
            ev = shipped.pop(0)
            if feasible is None:
                feasible = shipped.pop(0)
            if placed is None:
                placed = shipped.pop(0)
            usage_buf = np.empty(cluster.available.shape, np.float32)
        REGISTRY.incr("nomad.placer.staged_solves")
        # 1 when every node-set array the solve reads was on the device
        REGISTRY.incr("nomad.placer.stage_resident", int(not uploads))
        # steps the device loop will run, of the padded length its
        # program was compiled for
        REGISTRY.incr("nomad.placer.scan_steps", scan_steps(active))
        REGISTRY.incr("nomad.placer.scan_steps_padded", len(active))
        args = (avail, feasible, aff, placed, *spread, *dp, ev)
        return args, layout, usage_buf, extra_used, tgt.dev_groups

    def _solve_staged(self, ctx, tg, cluster, k, k_pad, staged):
        """Take _PER_EVAL_SOLVE_LOCK, solve, release -> (choices, founds,
        scores, ports, devices)."""
        # The usage gather -> solve -> in-flight registration runs
        # as ONE critical section across racing workers: the device
        # serializes launches anyway, and without this ordering two
        # concurrent evals both fill the same near-full best-fit
        # nodes to the brim and the applier rejects the loser's
        # whole node lists (the round-4 spread-rung rejection gap —
        # measured: overflows on the smallest-capacity nodes, base +
        # planned > available). Inside the lock each solve re-reads
        # usage WITH every earlier solve's overlay entries folded
        # (tensor/overlay.py), so racing workers interleave around
        # each other like the bulk path's carry provides for free.
        #
        # worker.solve covers the lock wait too: serialization
        # behind racing workers is exactly the stall the trace
        # should show. Its children split it, one set per
        # evaluation and task group: placer.lock_wait, then
        # placer.locked around gather / pack / ship / device_wait /
        # fetch / ports (a group that asks ports) / devices (a group
        # that asks devices) / register.
        # device=True mirrors each into the jax
        # profiler's trace, above the device's ops on one clock.
        with TRACER.span("worker.solve", k=k):
            with TRACER.span("placer.lock_wait", device=True):
                _PER_EVAL_SOLVE_LOCK.acquire()
            try:
                return self._solve_locked(ctx, tg, cluster, k, k_pad,
                                          staged)
            finally:
                _PER_EVAL_SOLVE_LOCK.release()

    def _solve_locked(self, ctx, tg, cluster, k, k_pad, staged):
        """One evaluation's usage gather -> solve -> in-flight
        registration; the caller holds _PER_EVAL_SOLVE_LOCK and staged
        the static arguments (_stage_statics) before taking it. Returns
        (choices, founds, scores) per request, k of k_pad of them, the
        ports of a group that asks them (_assign_ports) and the device
        instances of one that asks them (_assign_devices), chosen and
        registered here: the hold is what makes an evaluation's read of
        the ids taken and its own choice one step."""
        import jax

        from .kernels import solve_task_group_fused
        from .overlay import INFLIGHT

        args, layout, usage, extra_used, dev_groups = staged
        with TRACER.span("placer.locked", cpu=True, device=True, k=k,
                         k_pad=k_pad, n_pad=cluster.n_pad):
            with TRACER.span("placer.gather", device=True):
                cluster.refresh_usage(ctx, out=usage)

            with TRACER.span("placer.pack", device=True):
                if dev_groups is not None:
                    # racing evaluations' instances, as their usage is
                    # in the gather (this evaluation's own are its plan)
                    from .cluster import fold_inflight_devices

                    fold_inflight_devices(
                        extra_used, cluster, dev_groups,
                        INFLIGHT.devices_held(
                            getattr(ctx.snapshot, "index", None),
                            exclude_plan=ctx.plan))
                if extra_used is not None:
                    usage = np.concatenate([usage, extra_used], axis=1)

            # explicit shipment + shape-keyed window. ship is the
            # enqueue of the transfer and of the launch; device_wait is
            # what is left of the transfer, the launch latency and the
            # scan; the device_get of the ready result stays the
            # launch's one readback. Resident or the evaluation's own,
            # an argument has one shape and dtype: one program a key
            fused_key = ((usage.shape,) + tuple(a.shape for a in args)
                         + (layout,))
            with _warm_launch(solve_task_group_fused, fused_key,
                              _FUSED_WARM):
                with TRACER.span("placer.ship", device=True,
                                 bytes=usage.nbytes):
                    res = solve_task_group_fused(jax.device_put(usage),
                                                 *args, layout)
                with TRACER.span("placer.device_wait", device=True):
                    # the wait is split from the readback so the trace
                    # tells launch latency + scan from the copy back
                    # san-ok: same one sync as the device_get it precedes
                    jax.block_until_ready(res)
                with TRACER.span("placer.fetch", device=True):
                    out = jax.device_get(res)
                    choices = out[0].astype(np.int64)
                    founds = out[1] > 0.5
                    scores = out[2]
            touched = ports = held = devices = held_devices = None
            if self._wants_ports(ctx, tg):
                with TRACER.span("placer.ports", device=True) as span:
                    touched = np.unique(choices[founds], return_counts=True)
                    ports, held = self._assign_ports(ctx, tg, cluster,
                                                     *touched)
                    span.set(nodes=len(held),
                             ports=sum(map(len, held.values())))
            if ctx.tg_resources(tg).devices:
                with TRACER.span("placer.devices", device=True) as span:
                    touched = touched or np.unique(choices[founds],
                                                   return_counts=True)
                    devices, held_devices = self._assign_devices(
                        ctx, tg, cluster, *touched)
                    span.set(nodes=len(held_devices))
            with TRACER.span("placer.register", device=True):
                if ctx.plan is not None:
                    rows, counts = touched or np.unique(
                        choices[founds], return_counts=True)
                    INFLIGHT.register(
                        cluster, rows,
                        counts[:, None] * ctx.tg_vec(tg)[None, :], ctx.plan,
                        held, held_devices)
        return choices, founds, scores, ports, devices

    @staticmethod
    def _assign_ports(ctx, tg, cluster, rows, counts):
        """The exact port numbers of a group's found placements, chosen
        per touched node (`rows` of `cluster`, `counts[i]` placements
        on `rows[i]`) against ctx.port_index: lowest free first, so
        deterministic for what is taken. The caller holds
        _PER_EVAL_SOLVE_LOCK and registers the result in its in-flight
        entry before releasing it, so no evaluation of this process can
        read the same ports free. -> ({row: [a placement's
        AllocatedPorts, ...]}, {node id: [port numbers]}); a node that
        ran out holds fewer lists than placements. Work for the touched
        nodes only, each read through EvalContext.proposed_ports: a
        block's numbers come from its port column, no position of it is
        materialised."""
        from ..core.metrics import REGISTRY

        ask_res = ctx.tg_resources(tg)
        rows = rows.tolist()
        nodes = [cluster.nodes[ni] for ni in rows]
        ports: Dict[int, list] = {}
        held: Dict[str, list] = {}
        n_inflight = 0
        for ni, n, node, idx in zip(rows, counts.tolist(), nodes,
                                    ctx.port_indexes(nodes)):
            n_inflight += idx.inflight
            mine = ports[ni] = []
            for _ in range(n):
                got, err = idx.assign_ports(ask_res)
                if err:
                    break
                mine.append(got)
            # the row loop pops a node's placements off the end
            mine.reverse()
            if mine:
                held[node.id] = [p.value for got in mine for p in got]
        REGISTRY.incr("nomad.placer.port_nodes", len(nodes))
        REGISTRY.incr("nomad.placer.port_nodes_inflight", n_inflight)
        REGISTRY.incr("nomad.placer.ports_assigned",
                      sum(map(len, held.values())))
        return ports, held

    @staticmethod
    def _assign_devices(ctx, tg, cluster, rows, counts):
        """The device instances of a group's found placements, chosen
        per touched node (`rows` of `cluster`, `counts[i]` placements on
        `rows[i]`) against ctx.device_indexes: the node's groups that
        serve each ask, highest affinity first, then the emptiest, then
        the first free ids, so deterministic for what is taken. The
        caller holds _PER_EVAL_SOLVE_LOCK and registers the result in
        its in-flight entry before releasing it, so no evaluation of
        this process can read the same instances free. -> ({row: [a
        placement's {device group id: instance ids}, ...]}, {node id:
        {device group id: instance ids}}); a node that ran short holds
        fewer assignments than placements."""
        from ..core.metrics import REGISTRY

        asks = ctx.tg_resources(tg).devices
        nodes = [cluster.nodes[ni] for ni in rows.tolist()]
        devices: Dict[int, list] = {}
        held: Dict[str, dict] = {}
        n_inflight = n_assigned = 0
        for ni, n, node, idx in zip(rows.tolist(), counts.tolist(), nodes,
                                    ctx.device_indexes(nodes)):
            n_inflight += idx.inflight
            mine = devices[ni] = []
            for _ in range(n):
                got = idx.assign(asks, ctx.regex_cache, ctx.version_cache)
                if got is None:
                    break
                mine.append(got)
            # the row loop pops a node's placements off the end
            mine.reverse()
            if mine:
                on_node = held[node.id] = {}
                for got in mine:
                    for gid, ids in got.items():
                        on_node.setdefault(gid, []).extend(ids)
                        n_assigned += len(ids)
        REGISTRY.incr("nomad.placer.device_nodes", len(nodes))
        REGISTRY.incr("nomad.placer.device_nodes_inflight", n_inflight)
        REGISTRY.incr("nomad.placer.devices_assigned", n_assigned)
        return devices, held

    def _bulk_shape_ok(self, ctx, tg, tgt, k: int) -> bool:
        """Whether a BulkPlacementRequest of `k` placements of `tg` is a
        count solve: BestFit binpack with no spread / distinct-hosts /
        distinct_property semantics (fill-to-capacity is the exact greedy
        trajectory only for BestFit: the winner keeps winning until
        full; WorstFit and spread round-robin per placement), no port or
        device asks (per-placement ids), a feasibility mask the node set
        caches (none for CSI volumes or reserved ports) and k within the
        solver service's int16 counts. Every other group takes the
        per-placement scan. The per-request conditions (fresh, no
        ignore_node, no canary) hold for a bulk request by construction
        (scheduler/reconcile.py)."""
        from .solver import BulkSolverService

        if k > BulkSolverService.MAX_K or tgt.feas_base is None:
            return False
        if tgt.spread_alg or tgt.dh_job or tgt.dh_tg:
            return False
        if tgt.spread_rows or tgt.dp_rows:
            return False
        if tgt.extra_ask is not None and len(tgt.extra_ask):
            return False
        return not self._wants_ports(ctx, tg)

    def _solve_bulk_counts(self, ctx, cluster, tgt, k: int,
                           seed) -> np.ndarray:
        """The count solve, through the solver service (one
        device-resident usage carry, kernels.solve_bulk_multi) -> (N_pad,)
        int64 per-node counts."""
        # The service serializes ALL bulk solves — including
        # partial-commit retries (placed_tg/placed_job nonzero) — on one
        # device-resident carry, so racing workers can never
        # double-book. Retries routed around the service (the pre-r5
        # gate) solved against store-latest usage and collided with
        # each other, compounding the rejection cascade at 2M scale.
        # Cost: the carry solve drops the per-node anti-affinity term
        # for the retried remainder (a score preference, not a capacity
        # constraint; fresh solves have placed_* == 0).
        from .incremental import device_used_fn, free_epoch_fn
        from .solver import get_service

        static = cluster.static
        service = get_service()
        counts, solve_token = service.solve(
            static=static, feas_base=tgt.feas_base,
            aff=tgt.affinity_boost, ask=tgt.ask, k=k,
            tg_count=tgt.tg_count, seed=seed,
            used_fn=cluster.latest_usage,
            used_dev_fn=device_used_fn(cluster._store, static),
            free_epoch_fn=free_epoch_fn(cluster._store),
            joint=(self.algorithm == enums.SCHED_ALG_TPU_SOLVE))
        if ctx.plan is not None:
            ctx.plan.post_apply_hooks.append(
                lambda result, _t=solve_token: service.confirm(
                    _t, getattr(result, "rejected_nodes", None) or ()))
        return counts

    def _place_bulk_columnar(self, ctx, job, tg, bulk, cluster, tgt,
                             commit, seed, *, sched_batch: bool,
                             preemption_enabled: bool, attempt: int) -> None:
        """The C2M commit shape: one solve -> one AllocBlock. Host work
        is O(touched nodes), not O(K) — per-alloc ids/names materialize
        lazily from the block (structs/alloc.py AllocBlock)."""
        k = bulk.count
        counts = self._solve_bulk_counts(ctx, cluster, tgt, k, seed)
        # everything below is pure host work on fetched counts — under a
        # pipelined solver this "apply" window runs WHILE the device
        # solves the next batch; the span makes that overlap visible
        # next to solver.shard/solver.launch in the trace
        with TRACER.span("solver.apply", k=k):
            mean_score = self._bulk_trajectory_mean(counts, cluster, tgt)

            metrics = ctx.new_metrics()
            metrics.nodes_in_pool = len(cluster.nodes)
            metrics.nodes_evaluated = len(cluster.nodes)
            metrics.scores["bulk.normalized-score"] = mean_score

            nz = np.nonzero(counts)[0]
            placed_counts = counts[nz]
            total = int(placed_counts.sum())
            nodes = cluster.nodes
            commit.commit_block(
                tg,
                [nodes[int(ni)].id for ni in nz],
                [nodes[int(ni)].name for ni in nz],
                placed_counts.astype(np.int64),
                np.asarray(bulk.name_indices[:total], dtype=np.int64),
                mean_score)

        self._bulk_tail(ctx, job, tg, bulk.job_id,
                        bulk.name_indices[total:], metrics, cluster, tgt,
                        commit, sched_batch=sched_batch,
                        preemption_enabled=preemption_enabled,
                        attempt=attempt)

    # -- batched preemption: kernel node choice + host victim selection --

    def _preempt_batch(self, ctx, job, tg, reqs, cluster, tgt, commit, *,
                       sched_batch: bool, attempt: int, n_feasible: int,
                       invalidate=None) -> None:
        """Preemption for K unplaced requests as ONE in-kernel solve:
        kernels.preempt_solve picks each request's node (fit after
        eviction + the logistic preemption penalty) AND its concrete
        victims (priority-ascending prefix over the node's eligible
        victim column, carry-committed so siblings never double-claim).
        The host's remaining work per kernel row is one allocs_fit
        revalidation of the selected victim set (counted as
        victim_parity_checked) before the RankedNode commits.

        Span layout follows the work's new home: building the victim
        columns is tensor build (`worker.tensor_build`), the device/
        mirror launch is solver work (`solver.preempt`), revalidate +
        commit of kernel rows is `worker.preempt_commit`, and
        `worker.preempt` — the historically GC-noisy pure-Python host
        pass PERF.md tracks — now wraps ONLY the exact-scanner arm, so
        it reads ~0 when the kernel resolves every row."""
        from .cluster import build_victim_tensors

        with TRACER.span("worker.tensor_build", kind="victim_columns"):
            vt = build_victim_tensors(ctx, cluster, job.priority)
        k_pad = _pad_pow2(len(reqs), floor=1)
        active = np.zeros(k_pad, dtype=bool)
        active[: len(reqs)] = True
        with TRACER.span("solver.preempt", k=len(reqs)):
            picks, victims, flagged, scores = self._launch_preempt_solve(
                cluster, tgt, vt, active, k_pad)
        with TRACER.span("worker.preempt_commit", k=len(reqs)):
            self._preempt_batch_inner(
                ctx, job, tg, reqs, cluster, tgt, commit, vt,
                picks, victims, flagged, scores,
                sched_batch=sched_batch, attempt=attempt,
                n_feasible=n_feasible, invalidate=invalidate)

    def _preempt_batch_inner(self, ctx, job, tg, reqs, cluster, tgt,
                             commit, vt, picks, victims, flagged, scores,
                             *, sched_batch: bool, attempt: int,
                             n_feasible: int, invalidate=None) -> None:
        """Resolve the kernel's (pick, victim-set) rows into committed
        placements. The exact host scanner (NodeScorer.rank ->
        preempt_for_* + filterSuperset) survives as the fallback arm:
        rows the kernel flags (victim holds exact ports/devices), groups
        that need exact id assignment, reschedules carrying a node
        penalty, and revalidation misses. Those count as host_preempted
        — ~0 on the bulk path."""
        from ..scheduler.rank import NodeScorer
        from ..structs import allocs_fit
        from ..structs.alloc import Allocation

        nodes = cluster.nodes
        # exact port numbers / device instances / cores can't come from
        # the dense victim columns — those groups keep the host scanner
        exact_needed = (self._wants_ports(ctx, tg)
                        or self._wants_ids(ctx, tg))
        ask_vec = ctx.tg_vec(tg)

        scorer = NodeScorer(ctx, job, tg, algorithm=self._host_algorithm(),
                            preemption_enabled=True)
        # one shared metrics object for kernel rows (bulk-path idiom —
        # a per-alloc AllocMetric at K=512 is pure overhead); host-arm
        # rows keep per-row metrics the scorer populates
        kernel_metrics = ctx.new_metrics()
        kernel_metrics.nodes_in_pool = len(nodes)
        kernel_metrics.nodes_evaluated = len(nodes)
        # ProposedAllocs walks snapshot + plan rows per call; cache it
        # per node and drop the entry whenever a commit mutates that
        # node's plan, so repeat rows reuse the walk without ever
        # reading a stale victim list
        prop_cache: Dict[str, list] = {}

        def proposed(node_id: str):
            out = prop_cache.get(node_id)
            if out is None:
                out = prop_cache[node_id] = ctx.proposed_allocs(node_id)
            return out

        def host_metrics():
            m = ctx.new_metrics()
            m.nodes_in_pool = len(nodes)
            m.nodes_evaluated = len(nodes)
            return m

        n_kernel = n_host = n_parity = 0
        # a full host scan that found no node finds none for the next
        # request either (the same ask, no node penalty) until a commit
        # changes the plan: a job that can preempt nothing, placed again
        # by the preemption evaluation of a job that lost allocations,
        # scans once, not once a request
        scan_failed = False
        for i, req in enumerate(reqs):
            option = None
            kernel_row = False
            ni = int(picks[i])
            if req.ignore_node:
                # rescheduled alloc: the batched pick carries no
                # node-reschedule penalty, so keep the full host scan
                # (which weighs it) for these rare requests
                ni = -1
            if 0 <= ni < len(nodes):
                node = nodes[ni]
                if not exact_needed and not bool(flagged[i]):
                    ctx.metrics = kernel_metrics
                    option = self._commit_kernel_victims(
                        ctx, node, vt, ni, victims[i], float(scores[i]),
                        ask_vec, proposed, allocs_fit, Allocation)
                    n_parity += 1
                    kernel_row = option is not None
                if option is None:
                    # exact-resource group, flagged victim, or a
                    # revalidation miss: exact victim selection + scoring
                    # on the chosen node (ports/devices/spread handled by
                    # the scorer)
                    with TRACER.span("worker.preempt"):
                        host_metrics()
                        option = scorer.rank(node)
            if option is None and not kernel_row:
                if scan_failed and not req.ignore_node:
                    host_metrics()
                else:
                    # aggregate misprediction: full host scan for this one
                    with TRACER.span("worker.preempt"):
                        host_metrics()
                        option = self._preempt_fallback(
                            ctx, job, tg, nodes, req, sched_batch, attempt)
                    if option is None and not req.ignore_node:
                        scan_failed = True
            if option is not None:
                commit(req, option)
                scan_failed = False
                prop_cache.pop(option.node.id, None)
                scorer.record_placement(option.node)
                if invalidate is not None:
                    invalidate(option.node.id)
                if kernel_row:
                    n_kernel += 1
                else:
                    n_host += 1
                continue
            self._attribute_failure(ctx, ctx.metrics or host_metrics(),
                                    len(nodes), n_feasible)
            commit(req, None)
        _count_preempt(kernel_preempted=n_kernel, host_preempted=n_host,
                       victim_parity_checked=n_parity)

    def _launch_preempt_solve(self, cluster, tgt, vt, active, k_pad):
        """Run kernels.preempt_solve on-device (big shapes, under a
        jit_guard no_retrace window once the shape is warm) or through
        the numpy mirror (below PREEMPT_DEVICE_MIN, which see). Both
        arms return identical (picks, victims, flagged, scores) host
        arrays."""
        n_pad = cluster.n_pad
        if n_pad * k_pad < self.PREEMPT_DEVICE_MIN:
            return _preempt_solve_host(
                cluster.available, cluster.used.copy(), tgt.ask,
                tgt.feasible, vt.net_prio, active,
                vt.prio, vt.vec, vt.elig, vt.flagged)
        import jax

        from .kernels import preempt_solve

        f32 = np.float32
        args = (cluster.available.astype(f32), cluster.used.astype(f32),
                tgt.ask.astype(f32), tgt.feasible,
                vt.net_prio.astype(f32), active,
                vt.prio, vt.vec, vt.elig, vt.flagged)
        shape_key = (n_pad, k_pad, vt.v_pad, cluster.available.shape[1])
        # explicit shipment on BOTH arms: committed jax.Arrays and bare
        # numpy hit different jit cache entries, so a cold bare call
        # followed by a warm device_put call would read as a retrace
        dev = jax.device_put(args)
        with _warm_launch(preempt_solve, shape_key, _PREEMPT_WARM):
            out = jax.device_get(preempt_solve(*dev))
        picks, victims, flagged, scores = out
        return (np.asarray(picks), np.asarray(victims),
                np.asarray(flagged), np.asarray(scores))

    def _commit_kernel_victims(self, ctx, node, vt, ni, sel, score,
                               ask_vec, proposed, allocs_fit, Allocation):
        """Turn one kernel row (node ni + victim column mask) into a
        scored RankedNode, revalidating the post-eviction fit host-side
        with the exact AllocsFit (cores/ports collision semantics the
        dense columns can't see). Returns None on a revalidation miss —
        the caller re-routes that row through the exact scanner.

        The kernel's combined score is reused as the final score: its
        (fitness + preemption)/2 is the same mean the host scorer's
        binpack+preemption normalize() produces, evaluated against the
        solve's own carried usage — recomputing it per row was a third
        of the residual loop."""
        refs = vt.refs[ni] if ni < len(vt.refs) else []
        chosen = [refs[v] for v in np.nonzero(sel)[0] if v < len(refs)]
        prop = proposed(node.id)
        prop_ids = {a.id for a in prop}
        # a victim already evicted by an earlier host-arm row in this
        # batch is gone from proposed — its capacity is already free
        chosen = [a for a in chosen if a.id in prop_ids]
        victim_ids = {a.id for a in chosen}
        placement = Allocation(id="_cand", allocated_vec=ask_vec)
        remaining = [a for a in prop if a.id not in victim_ids]
        fit, _dim, _used_after = allocs_fit(node, remaining + [placement])
        if not fit:
            return None
        option = RankedNode(node=node)
        option.preempted_allocs = chosen or None
        option.final_score = score
        return option

    # -- a system group on every node: kernels.system_place --

    def place_system(self, ctx, job, tg, nodes, targets, *,
                     preemption_enabled: bool) -> "SystemPlacement":
        """One system (or sysbatch) group on each of `targets` (fresh
        nodes of `nodes`, the job's ready nodes in canonical order) in
        ONE launch: the cached feasibility mask, the fit, and on a node
        without room the victims by upstream's rule
        (kernels.system_place), read from the victim columns of
        build_system_victims. What the columns cannot model goes to the
        exact host scorer (NodeScorer.rank) node by node, in node order:
        a node whose victims hold ports or devices, and a node whose
        candidates' max_parallel penalty would read an eviction of an
        earlier node of this launch (the host's penalty counts the whole
        plan so far). The device's placements keep their order and get
        their port numbers under _PER_EVAL_SOLVE_LOCK, as the scan's do.
        Spans: system.place (args `nodes`, `preempting`) around it all,
        preempt.columns (args `nodes`, `victims`, `kept`: nodes read from
        the store's kept columns) around the columns."""
        from .cluster import build_system_victims

        with TRACER.span("system.place", nodes=len(targets)) as span:
            with TRACER.span("worker.tensor_build", n=len(nodes)):
                cluster = ClusterTensors.build(ctx, nodes)
            tgt = build_task_group_tensors(ctx, job, tg, cluster,
                                           algorithm=self.algorithm)
            target = np.zeros(cluster.n_pad, dtype=bool)
            index = cluster.node_index
            target[[index[n.id] for n in targets]] = True
            with TRACER.span("preempt.columns",
                             nodes=len(cluster.nodes)) as cspan:
                vt = build_system_victims(ctx, cluster, job.priority)
                cspan.set(victims=vt.n_victims, kept=vt.kept)
            _count_preempt(column_nodes=len(cluster.nodes),
                           kept_column_nodes=vt.kept)
            out = self._system_launch(ctx, tg, cluster, tgt, target, vt,
                                      preemption_enabled)
            self._system_fallbacks(ctx, job, tg, cluster, vt, out,
                                   preemption_enabled)
            span.set(preempting=out.n_preempting)
        return out

    def _system_launch(self, ctx, tg, cluster, tgt, target, vt,
                       preemption_enabled: bool) -> "SystemPlacement":
        """Gather usage, launch, fetch, choose ports and register the
        in-flight entry under _PER_EVAL_SOLVE_LOCK."""
        import jax

        from .kernels import system_place

        f = np.float64 if jax.config.jax_enable_x64 else np.float32
        n = len(cluster.nodes)
        static = (vt.prio.astype(f), vt.vec.astype(f), vt.valid,
                  vt.mp.astype(f), vt.grp, vt.cnt0.astype(f))
        shape_key = (cluster.n_pad, vt.v_pad, cluster.available.shape[1],
                     np.dtype(f).name)
        with TRACER.span("system.lock_wait", device=True):
            _PER_EVAL_SOLVE_LOCK.acquire()
        try:
            with TRACER.span("system.locked", device=True):
                cluster.refresh_usage(ctx)
                args = (cluster.available.astype(f), cluster.used.astype(f),
                        tgt.ask.astype(f), tgt.feasible, target,
                        np.bool_(preemption_enabled)) + static
                with _warm_launch(system_place, shape_key, _SYSTEM_WARM):
                    with TRACER.span("system.ship", device=True):
                        res = system_place(*jax.device_put(args))
                    with TRACER.span("system.fetch", device=True):
                        placed, victims, fitness = jax.device_get(res)
                out = SystemPlacement(cluster, vt, placed[:n], victims[:n],
                                      fitness[:n],
                                      target[:n] & tgt.feasible[:n])
                self._system_register(ctx, tg, cluster, vt, out)
        finally:
            _PER_EVAL_SOLVE_LOCK.release()
        return out

    def _system_register(self, ctx, tg, cluster, vt, out) -> None:
        """Under the lock: the device placements' ports, and their
        in-flight entry (usage and ports) for the evaluations racing
        behind this one."""
        from .overlay import INFLIGHT

        held = self._system_ports(ctx, tg, cluster, vt, out,
                                  out.device_rows())
        if ctx.plan is not None:
            rows = out.device_rows()
            INFLIGHT.register(
                cluster, rows,
                np.repeat(ctx.tg_vec(tg)[None, :], len(rows), 0),
                ctx.plan, held)

    @staticmethod
    def _system_ports(ctx, tg, cluster, vt, out, rows) -> dict:
        """The port numbers of the device's placements on `rows`, as
        _assign_ports chooses them (lowest free first): the lowest of
        the node's dynamic range where the node holds no port and the
        overlay none and the agent reserves none, else the node's
        ctx.port_index. A node out of numbers loses its placement (the
        host scorer fails it the same way). Sets out.labels and
        out.port_values by row; -> {node id: numbers}."""
        from .overlay import INFLIGHT

        ask = ctx.tg_resources(tg)
        labels = [label for net in ask.networks for label in net.dynamic_ports]
        if not labels:
            return {}
        nodes = cluster.nodes
        inflight = INFLIGHT.ports_on([nodes[r].id for r in rows.tolist()],
                                     getattr(ctx.snapshot, "index", None))
        values = np.zeros((len(out.placed), len(labels)), dtype=np.int32)
        held: Dict[str, list] = {}
        for r in rows.tolist():
            node = nodes[r]
            res = node.resources
            if (not vt.holds[r] and node.id not in inflight
                    and not node.reserved.reserved_ports
                    and res.min_dynamic_port + len(labels) - 1
                    <= res.max_dynamic_port):
                got = list(range(res.min_dynamic_port,
                                 res.min_dynamic_port + len(labels)))
            else:
                ports, err = ctx.port_index(node).assign_ports(ask)
                if err:
                    out.placed[r] = False
                    out.victims[r] = False
                    continue
                got = [p.value for p in ports]
            values[r] = got
            held[node.id] = got
        out.labels, out.port_values = labels, values
        return held

    def _system_fallbacks(self, ctx, job, tg, cluster, vt, out,
                          preemption_enabled: bool) -> None:
        """The nodes the columns cannot model, through the exact host
        scorer in node order, each with the max_parallel counts of the
        plan and of the nodes before it in this launch."""
        from ..scheduler.preemption import plan_preempted_counts
        from .overlay import INFLIGHT

        coupled = np.flatnonzero(
            out.victims.any(axis=1)
            & (vt.valid & (vt.mp > 0))[: len(out.victims)].any(axis=1))
        walk = sorted(out.exact_rows.union(coupled.tolist()))
        if not walk:
            return
        base = plan_preempted_counts(ctx.plan)
        extra: Dict[tuple, int] = {}
        scorer = NodeScorer(ctx, job, tg, preemption_enabled=preemption_enabled,
                            current_priority=job.priority)
        held: Dict[str, list] = {}
        for r in walk:
            coupled_cols = np.flatnonzero(vt.valid[r] & (vt.mp[r] > 0))
            mine = vt.groups_at(np.full(len(coupled_cols), r), coupled_cols)
            if r not in out.exact_rows and not any(
                    extra.get(key) for key in mine):
                chosen = np.flatnonzero(out.victims[r])
                for key in vt.groups_at(np.full(len(chosen), r), chosen):
                    extra[key] = extra.get(key, 0) + 1
                continue
            counts = dict(base)
            for key, c in extra.items():
                counts[key] = counts.get(key, 0) + c
            scorer.counts_override = counts
            metrics = ctx.new_metrics()
            metrics.nodes_evaluated += 1
            option = scorer.rank(cluster.nodes[r])
            out.fallback.append((r, option, metrics))
            out.exact_rows.add(r)
            for victim in (option.preempted_allocs or ()) if option else ():
                key = (victim.namespace, victim.job_id, victim.task_group)
                extra[key] = extra.get(key, 0) + 1
            if option is not None and option.allocated_ports:
                held[option.node.id] = [p.value
                                        for p in option.allocated_ports]
        if held and ctx.plan is not None:
            INFLIGHT.register(cluster, (), None, ctx.plan, held)

    @staticmethod
    def _bulk_trajectory_mean(counts: np.ndarray, cluster, tgt) -> float:
        """Exact mean normalized score over the greedy trajectory the
        bulk counts correspond to, computed host-side (the kernel scores
        a whole step at its start, which under-reports BestFit's rising
        fill scores). No spread/dp terms by bulk eligibility; mirrors
        kernels.score_nodes for the fit + anti-affinity + node-affinity
        sub-scores (reference funcs.go:236 ScoreFitBinPack,
        rank.go:596,710,800)."""
        nz = np.nonzero(counts)[0]
        if not len(nz):
            return 0.0
        c = counts[nz]
        total = int(c.sum())
        idx = np.repeat(nz, c)
        starts = np.concatenate([[0], np.cumsum(c)[:-1]])
        t = np.arange(total) - np.repeat(starts, c) + 1.0  # 1..c per node
        ask = np.asarray(tgt.ask, dtype=np.float64)
        avail = cluster.available[idx]
        used = cluster.used[idx] + t[:, None] * ask[None, :]
        fit = _binpack_fitness_np(avail, used)
        ptg_before = tgt.placed_tg[idx] + t - 1.0
        anti_present = ptg_before > 0
        anti = -(ptg_before + 1.0) / max(tgt.tg_count, 1.0)
        aff = tgt.affinity_boost[idx]
        aff_present = aff != 0.0
        dev = tgt.dev_affinity[idx] if tgt.dev_affinity is not None else 0.0
        dev_present = dev != 0.0 if tgt.dev_affinity is not None else False
        div = (1.0 + anti_present.astype(float) + aff_present.astype(float)
               + np.asarray(dev_present, dtype=float))
        score = (fit + np.where(anti_present, anti, 0.0) + aff
                 + np.where(dev_present, dev, 0.0)) / div
        return float(score.mean())

    @staticmethod
    def _attribute_failure(ctx, metrics, n_nodes: int, n_feasible: int) -> None:
        """Failure attribution the way the host path would do it: nodes
        masked by constraints/drivers are "filtered", nodes that passed
        feasibility but didn't fit are "exhausted" (reference feasible.go
        filter vs rank.go exhaust metrics)."""
        masked = n_nodes - n_feasible
        if masked:
            metrics.nodes_filtered += masked
            metrics.constraint_filtered["task group constraints"] = (
                metrics.constraint_filtered.get("task group constraints", 0)
                + masked)
        if n_feasible > 0:
            metrics.exhaust_node("resources")

    def _assign_cores(self, ctx, ask_res, numa_pol: str, ni: int, node,
                      option: RankedNode, core_used: Dict[int, set]) -> bool:
        """Post-solve core id assignment for one placement on the chosen
        node. Per-node sets live for the group's whole pass so sibling
        placements never double-book."""
        from ..scheduler.devices import select_cores, used_cores

        taken = core_used.get(ni)
        if taken is None:
            taken = core_used[ni] = used_cores(ctx.proposed_allocs(node.id))
        cores = select_cores(node, (), int(ask_res.cores), numa_pol,
                             taken=taken)
        if cores is None:
            return False
        taken.update(cores)
        option.allocated_cores = cores
        return True

    @staticmethod
    def _invalidate_node(cluster, node_id: str, *caches: Dict[int, object]) -> None:
        ni = cluster.node_index.get(node_id)
        if ni is not None:
            for cache in caches:
                cache.pop(ni, None)

    def _host_algorithm(self) -> str:
        return (enums.SCHED_ALG_BINPACK
                if self.algorithm in (enums.SCHED_ALG_TPU_BINPACK,
                                      enums.SCHED_ALG_TPU_SOLVE)
                else self.algorithm)

    def _host_one(self, ctx, job, tg, nodes, req, batch: bool,
                  preemption_enabled: bool, attempt: int) -> Optional[RankedNode]:
        penalty = frozenset({req.ignore_node}) if req.ignore_node else frozenset()
        return select_best_node(
            ctx, job, tg, nodes,
            batch=batch,
            algorithm=self._host_algorithm(),
            preemption_enabled=preemption_enabled,
            penalty_nodes=penalty,
            attempt=attempt,
        )

    def _preempt_fallback(self, ctx, job, tg, nodes, req, batch: bool,
                          attempt: int) -> Optional[RankedNode]:
        return self._host_one(ctx, job, tg, nodes, req, batch,
                              preemption_enabled=True, attempt=attempt)


class SystemPlacement:
    """What TPUPlacer.place_system decided, by node row of `cluster`:
    `placed` / `victims` / `fitness` as the launch returned them,
    `feasible` the target rows that passed feasibility, `exact_rows`
    the rows the host scorer decided instead (`fallback`: (row,
    RankedNode or None, its AllocMetric) in node order), `labels` and
    `port_values` (a row of numbers a node row) the ports of a group
    that asks them (None otherwise)."""

    def __init__(self, cluster, vt, placed, victims, fitness, feasible):
        self.cluster, self.vt = cluster, vt
        self.placed = np.array(placed, dtype=bool)
        self.victims = np.array(victims, dtype=bool)
        self.fitness, self.feasible = np.asarray(fitness), feasible
        # a victim that holds ports or devices: the host scorer's node
        self.exact_rows: set = set(np.flatnonzero(
            (self.victims & vt.flagged[: len(self.victims)]).any(axis=1)
        ).tolist())
        self.fallback: list = []
        self.labels = None
        self.port_values = None

    def device_rows(self) -> np.ndarray:
        keep = self.placed.copy()
        if self.exact_rows:
            keep[list(self.exact_rows)] = False
        return np.flatnonzero(keep)

    def block_ports(self, rows: np.ndarray):
        """The BlockPorts of the device's placements on `rows`, None for
        a group without ports."""
        from ..structs.alloc import BlockPorts

        if self.labels is None:
            return None
        return BlockPorts(list(self.labels), self.port_values[rows])

    @property
    def n_preempting(self) -> int:
        rows = self.device_rows()
        return int(self.victims[rows].any(axis=1).sum()) + sum(
            1 for _, option, _ in self.fallback
            if option is not None and option.preempted_allocs)

    def scores(self, rows: np.ndarray) -> np.ndarray:
        """The normalized score of the device's placements on `rows`,
        as NodeScorer.rank normalizes it: the binpack fit, and on a
        node that preempts the mean of that and the preemption score of
        its victims' net priority (rank.go netPriority)."""
        from ..scheduler.rank import preemption_score

        fit = self.fitness[rows].astype(np.float64)
        sel = self.victims[rows]
        prio = np.where(sel, self.vt.prio[rows], 0.0)
        top = prio.max(axis=1) if prio.size else np.zeros(len(rows))
        net = np.where(top > 0, top + prio.sum(axis=1)
                       / np.maximum(top, 1.0), 0.0)
        pscore = np.array([preemption_score(x) for x in net.tolist()])
        return np.where(sel.any(axis=1), (fit + pscore) / 2.0,
                        fit).astype(np.float32)


def _hold(option, ports: Dict[str, list], devices: Dict[str, dict]) -> None:
    """Add what a host-scored option holds on its node (port numbers,
    device instances) to the maps INFLIGHT.register takes."""
    if option is None:
        return
    node_id = option.node.id
    if option.allocated_ports:
        ports.setdefault(node_id, []).extend(
            p.value for p in option.allocated_ports)
    for gid, ids in (option.allocated_devices or {}).items():
        devices.setdefault(node_id, {}).setdefault(gid, []).extend(ids)
