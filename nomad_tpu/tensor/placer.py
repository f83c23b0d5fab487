"""TPUPlacer: batched placement behind SchedulerAlgorithm="tpu-binpack"
(the new algorithm value plugging into the reference's enum,
nomad/structs/operator.go:199-255).

Lowering strategy per evaluation:
  1. one ClusterTensors build (nodes + proposed usage),
  2. per task group: host-precompiled feasibility/affinity/spread arrays,
     device/core count columns, and distinct_property cap tables,
  3. one jitted solve_task_group scan placing all of the group's
     requests with full cross-placement visibility,
  4. commits mapped back through the scheduler's commit callback so the
     plan object and ctx.proposed_allocs stay authoritative. Exact port
     numbers, device instance ids, and core ids are assigned host-side
     per chosen node after the solve (counts were fit on-device): the
     ports while the solve lock is still held, so that they go into
     the in-flight overlay with the usage they belong to and racing
     evaluations never pick the same number (structs/network.py), the
     ids in the row loop. A group of fresh placements that needs none
     of them is committed as one AllocBlock, from the scan as from the
     count solve.

Preemption stays host-side: when the kernel finds no fit and preemption
is enabled, the per-request fallback runs the host NodeScorer preemption
path (reference rank.go:205-587's preemption fallback arm). A request
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
    ScoreFitBinPack), shared by the preemption pick mirror and the bulk
    trajectory mean. Thin wrapper over kernels._fit_scores_xp — the one
    formula the device kernels, the batch solver, and this host oracle
    all evaluate (parity pinned by test_batch_solver.py)."""
    from .kernels import fit_scores_np
    return fit_scores_np(available, used, spread_alg=False)


def _preempt_pick_host(available, used, evictable, ask, feasible, net_prio,
                       active) -> np.ndarray:
    """Numpy mirror of kernels.preempt_pick for small (nodes x requests)
    shapes — identical node ordering, no device round trip."""
    pscore = 1.0 / (1.0 + np.exp(0.0048 * (net_prio - 2048.0)))
    evictable = evictable.copy()
    picks = np.full(active.shape[0], -1, dtype=np.int32)
    neg = -1.0e30
    for i in range(active.shape[0]):
        if not active[i]:
            continue
        new_used = used + ask[None, :]
        deficit = np.maximum(new_used - available, 0.0)
        can = feasible & (deficit <= evictable).all(axis=1)
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
        picks[i] = best
        used[best] = np.minimum(used[best] + ask, available[best])
        evictable[best] = np.maximum(evictable[best] - deficit[best], 0.0)
    return picks


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
PREEMPT_STATS = {"kernel_preempted": 0, "host_preempted": 0,
                 "victim_parity_checked": 0}
_PREEMPT_STATS_LOCK = __import__("threading").Lock()
# shapes (n_pad, k_pad, v_pad, d) already compiled: later launches of the
# same shape run under a jit_guard no_retrace window (retrace there is a
# bug, not a warmup)
_PREEMPT_WARM: set = set()
# same discipline for the other placer-driven launch sites: per-eval
# fused solve, resident bulk solve, generic bulk solve
_FUSED_WARM: set = set()
_BULK_FUSED_WARM: set = set()
_BULK_WARM: set = set()


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
        if n:
            REGISTRY.incr(f"nomad.preempt.{key}", n)


# Per tensor build, how many Allocation deltas hit the event stream
# since the previous build anywhere in the process — the exact row
# count the O(Δ) scatter update (tensor/incremental.py) touches instead
# of a full O(nodes) rebuild. With an incremental feed attached to the
# build's store the count is feed-native (exact: Allocation events the
# feed actually drained, resyncs included); otherwise it falls back to
# the process-wide counter diff that seeded the ROADMAP item.
_DELTA_MARK_LOCK = __import__("threading").Lock()
_DELTA_MARK = [0.0]


def _changed_allocs_since_last_build(store=None) -> int:
    from ..core.metrics import REGISTRY

    if store is not None:
        from .incremental import feed_for

        feed = feed_for(store)
        if feed is not None:
            delta = float(feed.take_build_delta_count())
            REGISTRY.observe("nomad.worker.changed_allocs_per_build", delta)
            return int(delta)
    now = REGISTRY.get("nomad.events.alloc_deltas")
    with _DELTA_MARK_LOCK:
        prev, _DELTA_MARK[0] = _DELTA_MARK[0], now
    delta = max(0.0, now - prev)  # REGISTRY.reset between benches rewinds
    REGISTRY.observe("nomad.worker.changed_allocs_per_build", delta)
    return int(delta)


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
        with TRACER.span("worker.tensor_build", n=len(nodes),
                         changed_allocs=_changed_allocs_since_last_build(
                             getattr(ctx.snapshot, "_store", None))):
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
                if takes_blocks and self._bulk_shape_ok(ctx, tg, tgt):
                    with TRACER.span("worker.solve_bulk", k=bulk.count,
                                     columnar=True):
                        self._place_bulk_columnar(
                            ctx, job, tg, bulk, cluster, tgt, commit, seed,
                            sched_batch=batch,
                            preemption_enabled=preemption_enabled,
                            attempt=attempt)
                    continue
                if (takes_blocks and bulk.count > self.HOST_CUTOVER
                        and not self._wants_exact_ids(ctx, tg)):
                    # spread / distinct_hosts / distinct_property rule
                    # out the count solve, not the block: the scan's K
                    # placements are committed as ONE AllocBlock too
                    self._place_scan_columnar(
                        ctx, job, tg, bulk, cluster, tgt, commit, tie_perm,
                        sched_batch=batch,
                        preemption_enabled=preemption_enabled,
                        attempt=attempt)
                    continue
                # ports / devices / cores are assigned per placement on
                # its chosen node: expand and fall through (reusing the
                # tensors just built)
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
                for req in reqs:
                    option = self._host_one(ctx, job, tg, nodes, req,
                                            batch, preemption_enabled,
                                            attempt)
                    commit(req, option)
                    if option is not None and option.allocated_ports:
                        held.setdefault(option.node.id, []).extend(
                            p.value for p in option.allocated_ports)
                if held and ctx.plan is not None:
                    # the scorer read the overlay's ports (ctx.port_index);
                    # what it chose goes there in turn, for the
                    # evaluations racing behind it
                    from .overlay import INFLIGHT

                    INFLIGHT.register(cluster, (), None, ctx.plan, held)
                continue

            tgt = (prebuilt_tgt if prebuilt_tgt is not None
                   else build_task_group_tensors(ctx, job, tg, cluster,
                                                 algorithm=self.algorithm))

            if self._bulk_eligible(ctx, tg, reqs, tgt):
                with TRACER.span("worker.solve_bulk", k=len(reqs),
                                 columnar=False):
                    self._place_bulk(ctx, job, tg, reqs, cluster, tgt,
                                     commit, tie_perm, seed,
                                     sched_batch=batch,
                                     preemption_enabled=preemption_enabled,
                                     attempt=attempt)
                continue

            penalty_idx = np.full(_pad_pow2(len(reqs), floor=1), -1,
                                  dtype=np.int32)
            for i, req in enumerate(reqs):
                if req.ignore_node:
                    penalty_idx[i] = cluster.node_index.get(req.ignore_node, -1)
            choices, founds, scores, ports = self._scan_group(
                ctx, tg, cluster, tgt, len(reqs), penalty_idx, tie_perm)
            with TRACER.span("placer.rows", k=len(reqs)):
                self._place_rows(ctx, job, tg, reqs, cluster, tgt, commit,
                                 choices, founds, scores, ports,
                                 batch=batch,
                                 preemption_enabled=preemption_enabled,
                                 attempt=attempt)

    def _place_rows(self, ctx, job, tg, reqs, cluster, tgt, commit,
                    choices, founds, scores, ports, *, batch: bool,
                    preemption_enabled: bool, attempt: int) -> None:
        """The scan's placements handed over one row each: a RankedNode,
        an AllocMetric and (in commit) an Allocation a placement. Exact
        port numbers were chosen under the solve lock (`ports`:
        _assign_ports); device instances and core ids are assigned
        here, per chosen node (the kernel only fit-checked the counts),
        with per-node indexes that carry the assignments across this
        group's placements so they don't double-book."""
        nodes = cluster.nodes
        ask_res = ctx.tg_resources(tg)
        wants_devices = bool(ask_res.devices)
        wants_cores = bool(ask_res.cores)
        numa_pol = "none"
        if wants_cores:
            from ..scheduler.devices import combined_numa_affinity

            numa_pol = combined_numa_affinity(tg)
        dev_idx: Dict[int, object] = {}
        core_used: Dict[int, set] = {}

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
                if wants_devices or wants_cores:
                    ok = self._assign_ids(ctx, ask_res, numa_pol, ni, node,
                                          option, dev_idx, core_used)
                    if not ok:
                        # count-fit admitted a node the exact id
                        # assignment can't satisfy (NUMA require /
                        # overlapping asks): host selector for this
                        # request alone (its ports from ctx.port_index,
                        # which holds what the hold chose for the rest)
                        option = self._host_one(ctx, job, tg, nodes, req,
                                                batch, preemption_enabled,
                                                attempt)
                        commit(req, option)
                        if option is not None:
                            # the fallback assigned ids on its own
                            # node; drop that node's caches so later
                            # kernel placements rebuild them from the
                            # committed plan instead of double-booking
                            self._invalidate_node(
                                cluster, option.node.id,
                                dev_idx, core_used)
                        continue
                commit(req, option)
                continue
            if preemption_enabled:
                preempt_queue.append(req)
                continue
            self._attribute_failure(ctx, metrics, len(nodes), n_feasible)
            commit(req, None)
        if preempt_queue:
            self._preempt_batch(
                ctx, job, tg, preempt_queue, cluster, tgt, commit,
                sched_batch=batch, attempt=attempt,
                n_feasible=n_feasible,
                invalidate=lambda nid: self._invalidate_node(
                    cluster, nid, dev_idx, core_used))

    # -- bulk (count-based) solve: the C2M path --

    # Path-selection constants. Each was set from a launch cost seen in
    # an earlier environment; value not measured on the current chip
    # (ROADMAP D3).
    BULK_MIN = 256     # selects count solve vs per-placement scan
    BULK_STEP = 256    # placements assigned per scan step
    HOST_CUTOVER = 16  # selects host oracle (at/below) vs device launch
    # selects preempt_solve on the device (n_pad * k_pad at/above) vs
    # its numpy mirror
    PREEMPT_DEVICE_MIN = 1 << 18

    def _bulk_eligible(self, ctx, tg, reqs, tgt) -> bool:
        """K large, every request a fresh placement, BestFit binpack with
        no spread/distinct-hosts semantics (fill-to-capacity is only the
        exact greedy trajectory for BestFit: the winner keeps winning
        until full; WorstFit/spread round-robin per placement, which a
        batched step would mis-place — measured, not guessed), and
        nothing that needs per-alloc host-side id assignment (exact
        ports, device instances, cores) or distinct_property tables."""
        if len(reqs) < self.BULK_MIN:
            return False
        if tgt.spread_alg or tgt.dh_job or tgt.dh_tg:
            return False
        if tgt.spread_val_id.shape[0]:
            return False
        if tgt.extra_ask is not None and len(tgt.extra_ask):
            return False
        if tgt.dp_val_id is not None and tgt.dp_val_id.shape[0]:
            return False
        ask_res = ctx.tg_resources(tg)
        if ask_res.reserved_port_asks() or ask_res.dynamic_port_count():
            return False
        return all(req.previous_alloc is None and not req.ignore_node
                   and not req.canary for req in reqs)

    @staticmethod
    def _wants_ports(ctx, tg) -> bool:
        ask_res = ctx.tg_resources(tg)
        return bool(ask_res.reserved_port_asks()
                    or ask_res.dynamic_port_count())

    @staticmethod
    def _wants_exact_ids(ctx, tg) -> bool:
        """Whether a placement of this group carries something assigned
        on its chosen node after the solve (exact port numbers, device
        instances, core ids): the row loop's wants_ports /
        wants_devices / wants_cores as one test."""
        ask_res = ctx.tg_resources(tg)
        return bool(ask_res.reserved_port_asks()
                    or ask_res.dynamic_port_count()
                    or ask_res.devices or ask_res.cores)

    def _scan_group(self, ctx, tg, cluster, tgt, k: int, penalty_idx,
                    tie_perm, columnar: bool = False):
        """One group's per-placement scan of k placements: admitted,
        staged outside _PER_EVAL_SOLVE_LOCK, solved under it ->
        (choices, founds, scores, ports): the first three of the padded
        length, the last _assign_ports' result for a group that asks
        ports, else None.
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
        chosen node, each with its own name index and score."""
        k = bulk.count
        # a bulk request has no ignore_node: no reschedule penalty
        penalty_idx = np.full(_pad_pow2(k, floor=1), -1, dtype=np.int32)
        choices, founds, scores, _ = self._scan_group(
            ctx, tg, cluster, tgt, k, penalty_idx, tie_perm, columnar=True)

        nodes = cluster.nodes
        metrics = ctx.new_metrics()
        metrics.nodes_in_pool = len(nodes)
        metrics.nodes_evaluated = len(nodes)
        name_indices = np.asarray(bulk.name_indices, dtype=np.int64)
        found = np.flatnonzero(founds[:k])
        if len(found):
            # np.unique's sorted rows are the stable order's node runs
            found = found[np.argsort(choices[found], kind="stable")]
            rows, counts = np.unique(choices[found], return_counts=True)
            rows = rows.tolist()
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
                nodes_in_pool=len(nodes))

        self._bulk_tail(ctx, job, tg, bulk.job_id,
                        name_indices[~founds[:k]], metrics, cluster, tgt,
                        commit, sched_batch=sched_batch,
                        preemption_enabled=preemption_enabled,
                        attempt=attempt)

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
        """Pack the fused solve's static arguments and put them on the
        device, before _PER_EVAL_SOLVE_LOCK is taken: capacity, this
        job's own placement counts, feasibility, affinities, the tie
        permutation, the step, spread and distinct_property tables and
        the scalars are all fixed once the group's tensors are built.
        -> (device arrays, usage buffer, extra usage columns or None).
        The usage buffer is this group's (n_pad, D) f32 gather target,
        allocated here so that the hold does not; the extra columns
        (device / core counts, this evaluation's own view) extend it
        under placer.pack."""
        import jax

        from ..core.metrics import REGISTRY
        from .kernels import pack_solve_args, scan_steps

        with TRACER.span("placer.stage", device=True) as span:
            extra_used = None
            avail, ask = cluster.available, tgt.ask
            if tgt.extra_ask is not None and len(tgt.extra_ask):
                # device/core count columns extend the dense dims
                avail = np.concatenate([avail, tgt.extra_cap], axis=1)
                ask = np.concatenate([ask, tgt.extra_ask])
                extra_used = np.asarray(tgt.extra_used, np.float32)
            packed = pack_solve_args(
                avail, tgt.placed_tg, tgt.placed_job, ask, tgt.feasible,
                tgt.affinity_boost, penalty_idx, active,
                tgt.spread_val_id, tgt.spread_val_ok, tgt.spread_counts,
                tgt.spread_desired, tgt.spread_has_targets,
                tgt.spread_weight,
                -1.0, tgt.tg_count, tgt.dh_job, tgt.dh_tg, tgt.spread_alg,
                dev_affinity=tgt.dev_affinity,
                dp_val_id=tgt.dp_val_id, dp_val_ok=tgt.dp_val_ok,
                dp_counts0=tgt.dp_counts, dp_limit=tgt.dp_limit,
                tie_perm=tie_perm)
            span.set(bytes=sum(a.nbytes for a in packed))
            dev = jax.device_put(packed)
            usage_buf = np.empty(cluster.available.shape, np.float32)
        REGISTRY.incr("nomad.placer.staged_solves")
        # steps the device loop will run, of the padded length its
        # program was compiled for
        REGISTRY.incr("nomad.placer.scan_steps", scan_steps(active))
        REGISTRY.incr("nomad.placer.scan_steps_padded", len(active))
        return dev, usage_buf, extra_used

    def _solve_staged(self, ctx, tg, cluster, k, k_pad, staged):
        """Take _PER_EVAL_SOLVE_LOCK, solve, release -> (choices, founds,
        scores, ports)."""
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
        # fetch / ports (a group that asks ports) / register.
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
        (choices, founds, scores) per request, k of k_pad of them, and
        the ports of a group that asks them (_assign_ports), chosen and
        registered here: the hold is what makes an evaluation's read of
        the ports taken and its own choice one step."""
        import jax

        from .kernels import solve_task_group_fused
        from .overlay import INFLIGHT

        statics, usage, extra_used = staged
        with TRACER.span("placer.locked", cpu=True, device=True, k=k,
                         k_pad=k_pad, n_pad=cluster.n_pad):
            with TRACER.span("placer.gather", device=True):
                cluster.refresh_usage(ctx, out=usage)

            with TRACER.span("placer.pack", device=True):
                if extra_used is not None:
                    usage = np.concatenate([usage, extra_used], axis=1)

            # explicit shipment + shape-keyed window. ship is the
            # enqueue of the transfer and of the launch; device_wait is
            # what is left of the transfer, the launch latency and the
            # scan; the device_get of the ready result stays the
            # launch's one readback
            fused_key = (usage.shape,) + tuple(a.shape for a in statics)
            with _warm_launch(solve_task_group_fused, fused_key,
                              _FUSED_WARM):
                with TRACER.span("placer.ship", device=True,
                                 bytes=usage.nbytes):
                    res = solve_task_group_fused(jax.device_put(usage),
                                                 *statics)
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
            touched = ports = held = None
            if self._wants_ports(ctx, tg):
                with TRACER.span("placer.ports", device=True) as span:
                    touched = np.unique(choices[founds], return_counts=True)
                    ports, held = self._assign_ports(ctx, tg, cluster,
                                                     *touched)
                    span.set(nodes=len(held),
                             ports=sum(map(len, held.values())))
            with TRACER.span("placer.register", device=True):
                if ctx.plan is not None:
                    rows, counts = touched or np.unique(
                        choices[founds], return_counts=True)
                    INFLIGHT.register(
                        cluster, rows,
                        counts[:, None] * ctx.tg_vec(tg)[None, :], ctx.plan,
                        held)
        return choices, founds, scores, ports

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
        nodes only; on a populated node proposed_allocs materialises
        the rows of its blocks (ROADMAP C2)."""
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

    def _bulk_shape_ok(self, ctx, tg, tgt) -> bool:
        """Task-group-level bulk eligibility (the per-request conditions
        of _bulk_eligible hold for a BulkPlacementRequest by
        construction)."""
        if tgt.spread_alg or tgt.dh_job or tgt.dh_tg:
            return False
        if tgt.spread_val_id.shape[0]:
            return False
        if tgt.extra_ask is not None and len(tgt.extra_ask):
            return False
        if tgt.dp_val_id is not None and tgt.dp_val_id.shape[0]:
            return False
        ask_res = ctx.tg_resources(tg)
        if ask_res.reserved_port_asks() or ask_res.dynamic_port_count():
            return False
        return True

    def _solve_bulk_counts(self, ctx, cluster, tgt, k: int, seed,
                           tie_perm) -> np.ndarray:
        """Run the count-based bulk solve through whichever backend fits
        (solver service with device-resident carry > fused resident
        arrays > generic kernel) -> (N_pad,) int64 per-node counts."""
        from .kernels import solve_bulk, solve_bulk_fused
        from .solver import BulkSolverService

        k_pad = _pad_pow2(k, floor=self.BULK_STEP)
        n_steps = k_pad // self.BULK_STEP
        static = cluster.static
        if (static is not None and tgt.feas_base is not None
                and k <= BulkSolverService.MAX_K):
            # The service path serializes ALL bulk solves — including
            # partial-commit retries (placed_tg/placed_job nonzero) —
            # on one device-resident carry, so racing workers can never
            # double-book. Retries routed around the service (the pre-r5
            # gate) solved against store-latest usage and collided with
            # each other, compounding the rejection cascade at 2M scale.
            # Cost: the carry solve drops the per-node anti-affinity
            # term for the retried remainder (a score preference, not a
            # capacity constraint; fresh solves have placed_* == 0).
            from .incremental import device_used_fn, free_epoch_fn
            from .solver import get_service

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
        if static is not None and tgt.feas_base is not None:
            from .solver import ensure_resident

            f32 = np.float32
            avail_dev, feas_dev, aff_dev = ensure_resident(
                static, tgt.feas_base, tgt.affinity_boost)
            import jax

            dyn = np.concatenate(
                [cluster.used, tgt.placed_tg[:, None],
                 tgt.placed_job[:, None]], axis=1).astype(f32)
            # avail/feas/aff are already device-resident (device_put of
            # a committed array is a no-op); ship the per-solve host
            # args explicitly — scalars included, an implicit scalar
            # transfer trips the warm window's transfer guard
            host = jax.device_put((dyn, tgt.ask.astype(f32), np.int32(k),
                                   f32(tgt.tg_count), np.uint32(seed)))
            fused_key = (dyn.shape, tgt.ask.shape,
                         np.shape(avail_dev), n_steps)
            with _warm_launch(solve_bulk_fused, fused_key,
                              _BULK_FUSED_WARM):
                out = jax.device_get(solve_bulk_fused(
                    avail_dev, feas_dev, aff_dev, *host,
                    batch=self.BULK_STEP, n_steps=n_steps))
            return out.astype(np.int64)
        import jax

        args = (cluster.available, cluster.used, tgt.ask, tgt.feasible,
                tgt.placed_tg, tgt.placed_job, tgt.affinity_boost,
                np.zeros(cluster.n_pad), tgt.spread_val_id,
                tgt.spread_val_ok, tgt.spread_counts, tgt.spread_desired,
                tgt.spread_has_targets, tgt.spread_weight, np.int32(k),
                tgt.tg_count, tgt.dh_job, tgt.dh_tg, tgt.spread_alg,
                tie_perm)
        dev = jax.device_put(args)
        bulk_key = tuple(np.shape(a) for a in args) + (n_steps,)
        with _warm_launch(solve_bulk, bulk_key, _BULK_WARM):
            out = jax.device_get(solve_bulk(
                *dev, batch=self.BULK_STEP, n_steps=n_steps))
        return out.astype(np.int64)

    def _place_bulk_columnar(self, ctx, job, tg, bulk, cluster, tgt,
                             commit, seed, *, sched_batch: bool,
                             preemption_enabled: bool, attempt: int) -> None:
        """The C2M commit shape: one solve -> one AllocBlock. Host work
        is O(touched nodes), not O(K) — per-alloc ids/names materialize
        lazily from the block (structs/alloc.py AllocBlock)."""
        k = bulk.count
        tie_perm = None  # only the generic kernel consumes it
        if cluster.static is None or tgt.feas_base is None:
            tie_perm = np.random.default_rng(seed).permutation(
                cluster.n_pad).astype(np.int32)
        counts = self._solve_bulk_counts(ctx, cluster, tgt, k, seed, tie_perm)
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

    def _place_bulk(self, ctx, job, tg, reqs, cluster, tgt, commit,
                    tie_perm, seed, *, sched_batch: bool,
                    preemption_enabled: bool, attempt: int) -> None:
        """Place K identical requests as per-node COUNTS from one
        solve_bulk launch (one readback regardless of K), then commit
        through the scheduler's normal commit callback so plan assembly
        stays authoritative. With a cached ClusterStatic the fused entry
        runs against device-resident capacity/mask/affinity arrays and
        ships only the (N, D+2) dynamic matrix + scalars per eval."""
        k = len(reqs)
        counts = self._solve_bulk_counts(ctx, cluster, tgt, k, seed, tie_perm)
        mean_score = self._bulk_trajectory_mean(counts, cluster, tgt)

        # one shared metrics object for the whole group: per-alloc
        # AllocMetric at bulk scale is pure overhead (the mean normalized
        # score is what the benches and the eval summary consume)
        metrics = ctx.new_metrics()
        metrics.nodes_in_pool = len(cluster.nodes)
        metrics.nodes_evaluated = len(cluster.nodes)
        metrics.scores["bulk.normalized-score"] = mean_score

        commit_many = getattr(commit, "commit_many", None)
        pos = 0
        if commit_many is not None:
            for ni in np.nonzero(counts)[0]:
                c = int(counts[ni])
                commit_many(tg, cluster.nodes[ni], reqs[pos:pos + c],
                            mean_score)
                pos += c
        else:
            for ni in np.nonzero(counts)[0]:
                node = cluster.nodes[ni]
                for _ in range(int(counts[ni])):
                    req = reqs[pos]
                    pos += 1
                    option = RankedNode(node=node)
                    option.final_score = mean_score
                    commit(req, option)
        unplaced = reqs[pos:]
        if not unplaced:
            return
        n_feasible = int(tgt.feasible[: len(cluster.nodes)].sum())
        if preemption_enabled:
            self._preempt_batch(ctx, job, tg, unplaced, cluster, tgt,
                                commit, sched_batch=sched_batch,
                                attempt=attempt, n_feasible=n_feasible)
            return
        for req in unplaced:
            metrics = ctx.new_metrics()
            metrics.nodes_in_pool = len(cluster.nodes)
            metrics.nodes_evaluated = len(cluster.nodes)
            self._attribute_failure(ctx, metrics, len(cluster.nodes),
                                    n_feasible)
            commit(req, None)

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
        exact_needed = self._wants_exact_ids(ctx, tg)
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
                # aggregate misprediction: full host scan for this one
                with TRACER.span("worker.preempt"):
                    host_metrics()
                    option = self._preempt_fallback(ctx, job, tg, nodes,
                                                    req, sched_batch,
                                                    attempt)
            if option is not None:
                commit(req, option)
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

    def _assign_ids(self, ctx, ask_res, numa_pol: str, ni: int, node,
                    option: RankedNode, dev_idx: Dict[int, object],
                    core_used: Dict[int, set]) -> bool:
        """Post-solve concrete id assignment for one placement on the
        chosen node. Per-node indexes live for the group's whole pass so
        sibling placements never double-book. A False return leaves any
        staged device instances reserved — conservative, and only
        reachable on count-fit mispredictions."""
        from ..scheduler.devices import DeviceIndex, select_cores, used_cores

        proposed = None
        if ask_res.devices:
            idx = dev_idx.get(ni)
            if idx is None:
                proposed = ctx.proposed_allocs(node.id)
                idx = dev_idx[ni] = DeviceIndex(node, proposed)
            assignment = idx.assign(ask_res.devices, ctx.regex_cache,
                                    ctx.version_cache)
            if assignment is None:
                return False
            option.allocated_devices = assignment
        if ask_res.cores:
            taken = core_used.get(ni)
            if taken is None:
                if proposed is None:
                    proposed = ctx.proposed_allocs(node.id)
                taken = core_used[ni] = used_cores(proposed)
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
