"""JAX placement kernels.

Reproduces the reference scoring pipeline (scheduler/rank.go:205-835,
nomad/structs/funcs.go:236-278, scheduler/spread.go) as dense vector math
over all nodes at once, and the greedy placement loop
(generic_sched.go:511 computePlacements) as a device loop whose carry is
the cluster usage state — so each placement sees every earlier one, the
same commit-visibility contract the host path gets via
ctx.proposed_allocs.

Where the host path subsamples candidates (limit = max(2, ceil(log2 N)),
reference stack.go:82-95), the kernel scores *all* nodes and argmaxes —
strictly better placements at the same asymptotic cost, because the MXU
eats the (K x N) score matrix whole.

Shapes (padded to powers of two by the caller for compile-cache reuse):
  N nodes, D=3 resource dims, K placements, S spread attrs, V interned
  attribute-value vocabulary.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG = -1.0e30  # "infeasible" score sentinel
# additive tie-break jitter for the bulk engine's sort key (see
# solve_bulk_multi). Sized between the two constraints: far below any
# meaningful score gap (normalized scores live in [0, ~1.5] and the
# bench's score-parity margin is ~1e-3), far ABOVE the f32 ulp at the
# top of that range (np.spacing(1.0f) = 1.19e-7 — a jitter at or below
# the ulp would be rounded away exactly where BestFit ties concentrate,
# collapsing racing workers onto the same nodes again)
TIE_JITTER = 3.0e-5
BINPACK_MAX_FIT_SCORE = 18.0  # reference scheduler/rank.go:18


def _free_fractions_xp(xp, available, used):
    """Free fraction per (node, dim) after `used` is placed
    (reference funcs.go:213 computeFreePercentage).

    x/0 capacity -> -inf free (its 10^free term vanishes); 0/0 -> 0.0.

    `xp` is the array namespace (jnp on the device path, numpy on the
    host oracle) — the ONE copy of the formula, so the host fallback,
    the greedy kernel, and the batch solver cannot drift apart.
    """
    safe = xp.where(available > 0, available, 1.0)
    ratio = xp.where(
        available > 0,
        used / safe,
        xp.where(used > 0, xp.inf, 0.0),
    )
    return 1.0 - ratio


def _fit_scores_xp(xp, available, used, spread_alg):
    free = _free_fractions_xp(xp, available, used)
    total = 10.0 ** free[..., 0] + 10.0 ** free[..., 1]
    binpack = xp.clip(20.0 - total, 0.0, BINPACK_MAX_FIT_SCORE)
    spread = xp.clip(total - 2.0, 0.0, BINPACK_MAX_FIT_SCORE)
    return xp.where(spread_alg, spread, binpack) / BINPACK_MAX_FIT_SCORE


def _free_fractions(available: jnp.ndarray, used: jnp.ndarray) -> jnp.ndarray:
    return _free_fractions_xp(jnp, available, used)


def fit_scores(available: jnp.ndarray, used: jnp.ndarray,
               spread_alg: jnp.ndarray) -> jnp.ndarray:
    """Normalized fit score per node in [0, 1].

    binpack (BestFit-v3): clip(20 - (10^freeCpu + 10^freeMem), 0, 18)/18
    spread  (WorstFit):   clip((10^freeCpu + 10^freeMem) - 2, 0, 18)/18
    (reference funcs.go:236 ScoreFitBinPack / :263 ScoreFitSpread)
    """
    return _fit_scores_xp(jnp, available, used, spread_alg)


def fit_scores_np(available, used, spread_alg=False):
    """Numpy twin of `fit_scores` — same `_fit_scores_xp` core, so the
    host oracle (`tensor/placer._binpack_fitness_np`), the tests, and
    the bench score the exact formula the kernels run on device."""
    import numpy as np
    return _fit_scores_xp(np, np.asarray(available, dtype=np.float64),
                          np.asarray(used, dtype=np.float64), spread_alg)


def _pairwise_sum_xp(xp, v):
    """Fixed-tree pairwise sum over the LEADING axis. A plain ``.sum()``
    leaves the float add order to the backend's reduction strategy,
    which varies with the surrounding fusion context — the same
    contributions summed inside two different compiled graphs
    (single-device vs mesh-sharded) can disagree in the last ulp, and
    that is enough to flip a near-tied selection. Explicit halving adds
    pin the association order by shape alone, so every layout reduces
    identically bit-for-bit. 1-D input reduces to a scalar; (S, ...)
    input reduces axis 0 elementwise (the jnp.sum(x, axis=0) twin)."""
    n = int(v.shape[0])
    p = 1
    while p < n:
        p *= 2
    if p != n:
        v = xp.concatenate(
            [v, xp.zeros((p - n,) + tuple(v.shape[1:]), dtype=v.dtype)])
    while v.shape[0] > 1:
        v = v[0::2] + v[1::2]
    return v[0]


def score_nodes(
    *,
    available,        # (N, D) node capacity minus reserved; D = 4 base
                      #        dims + one column per device ask + one for
                      #        reserved cores when the group asks for them
    used,             # (N, D) current proposed usage
    ask,              # (D,)   task-group resource ask
    feasible,         # (N,)   bool: constraints+drivers+devices mask
    placed_tg,        # (N,)   proposed allocs of this job+tg per node
    placed_job,       # (N,)   proposed allocs of this job per node
    affinity_boost,   # (N,)   precomputed sum(weight)/sum|weight| per node
    dev_affinity,     # (N,)   device-affinity sub-score per node (0 = absent)
    penalty_idx,      # ()     node index to penalize (-1 = none)
    spread_val_id,    # (S, N) interned spread-attr value per node
    spread_val_ok,    # (S, N) bool: node has the attribute
    spread_counts,    # (S, V) combined existing+proposed counts per value
    spread_desired,   # (S, V) desired count per value (NaN = no target)
    spread_has_targets,  # (S,) bool: explicit targets vs even-spread
    spread_weight,    # (S,)  weight / sum|weights|
    dp_val_id,        # (P, N) interned distinct_property value per node
    dp_val_ok,        # (P, N) bool: node has the property
    dp_counts,        # (P, Vd) proposed alloc count per property value
    dp_limit,         # (P,)   max allocs per value (propertyset rtarget)
    lowest_boost,     # ()    running minimum explicit boost (spread.go)
    tg_count,         # ()    task group desired count
    dh_job,           # ()    bool: job-level distinct_hosts
    dh_tg,            # ()    bool: group-level distinct_hosts
    spread_alg,       # ()    bool: WorstFit instead of BestFit
    spread_counts_at=None,   # (S, N) spread_counts at each node's own value
    spread_desired_at=None,  # (S, N) spread_desired likewise
    dp_counts_at=None,       # (P, N) dp_counts likewise
):
    """Score every node for one placement. Returns (score, fitness) each
    (N,) and the (S, N) spread boost; infeasible nodes score NEG.

    The three `*_at` arguments are the tables read at each node's own
    value. A caller that carries them from placement to placement (the
    per-placement loop) passes them; left out, they are looked up here.

    Mirrors the host oracle NodeScorer.rank (scheduler/rank.py): the final
    score is the *mean of the sub-scores that apply* (reference
    rank.go:800 ScoreNormalizationIterator) — each sub-score carries a
    presence flag and the divisor is the number of present sub-scores.
    Fit scoring only reads the first two columns (cpu, mem — reference
    funcs.go:213), so the appended device/core columns participate in
    feasibility without perturbing the score.
    """
    # The named scopes (feasibility / score / spread here, select /
    # usage_update in the placement loop) put each HLO op of the step
    # under its phase in the profiler's trace; they change no computation.
    n = available.shape[0]
    new_used = used + ask[None, :]

    with jax.named_scope("feasibility"):
        ok = feasible & jnp.all(new_used <= available, axis=1)
        ok &= jnp.where(dh_job, placed_job == 0, True)
        ok &= jnp.where(dh_tg, placed_tg == 0, True)

        # distinct_property cap (reference scheduler/propertyset.go via
        # feasible.go:649 DistinctPropertyIterator): a node is infeasible
        # if it lacks the property or its value's proposed count is at
        # the limit
        if dp_val_id.shape[0]:
            dp_at = dp_counts_at                                   # (P, N)
            if dp_at is None:
                dp_at = jnp.take_along_axis(dp_counts, dp_val_id, axis=1)
            dp_ok = dp_val_ok & (dp_at < dp_limit[:, None])
            ok &= jnp.all(dp_ok, axis=0)

    with jax.named_scope("score"):
        fitness = fit_scores(available, new_used, spread_alg)

        # job anti-affinity (reference rank.go:596)
        anti_present = placed_tg > 0
        anti = (-(placed_tg.astype(fitness.dtype) + 1.0)
                / jnp.maximum(tg_count, 1.0))

        # node rescheduling penalty (reference rank.go:666)
        resched_present = jnp.arange(n) == penalty_idx

        # node affinity (reference rank.go:710); boost precomputed
        # host-side
        aff_present = affinity_boost != 0.0

        # device affinity (host oracle's separate "device-affinity"
        # sub-score; reference rank.go folds the deviceAllocator offer
        # score in)
        dev_present = dev_affinity != 0.0

    with jax.named_scope("spread"):
        spread_total, boost = _spread_boost(
            fitness.dtype, spread_val_id, spread_val_ok, spread_counts,
            spread_desired, spread_has_targets, spread_weight, lowest_boost,
            spread_counts_at, spread_desired_at)
        spread_present = spread_total != 0.0

    with jax.named_scope("score"):
        divisor = (
            1.0
            + anti_present.astype(fitness.dtype)
            + resched_present.astype(fitness.dtype)
            + aff_present.astype(fitness.dtype)
            + dev_present.astype(fitness.dtype)
            + spread_present.astype(fitness.dtype)
        )
        total = (
            fitness
            + jnp.where(anti_present, anti, 0.0)
            + jnp.where(resched_present, -1.0, 0.0)
            + jnp.where(aff_present, affinity_boost, 0.0)
            + jnp.where(dev_present, dev_affinity, 0.0)
            + jnp.where(spread_present, spread_total, 0.0)
        )
        final = total / divisor
        return jnp.where(ok, final, NEG), fitness, boost


def _spread_boost(dtype, spread_val_id, spread_val_ok, spread_counts,
                  spread_desired, spread_has_targets, spread_weight,
                  lowest_boost, counts_at=None, desired=None):
    """The spread sub-score of score_nodes -> ((N,) total, (S, N) boost)
    (reference spread.go:128 + propertyset.go). `counts_at` and
    `desired` are the two (S, V) tables at each node's own value."""
    if counts_at is None:
        counts_at = jnp.take_along_axis(spread_counts, spread_val_id, axis=1)  # (S, N)
    used_cnt = counts_at.astype(dtype) + 1.0  # incl. this placement
    if desired is None:
        desired = jnp.take_along_axis(spread_desired, spread_val_id, axis=1)   # (S, N)

    explicit = jnp.where(
        jnp.isnan(desired),
        -1.0,
        jnp.where(
            desired == 0.0,
            lowest_boost,
            (desired - used_cnt) / jnp.where(desired == 0.0, 1.0, desired)
            * spread_weight[:, None],
        ),
    )
    explicit = jnp.where(spread_val_ok, explicit, -1.0)

    # even-spread boost (reference spread.go evenSpreadScoreBoost): uses
    # combined counts *without* the current placement
    present_v = spread_counts > 0                                   # (S, V)
    any_present = jnp.any(present_v, axis=1)                        # (S,)
    minc = jnp.min(jnp.where(present_v, spread_counts, jnp.iinfo(jnp.int32).max),
                   axis=1).astype(dtype)                            # (S,)
    maxc = jnp.max(jnp.where(present_v, spread_counts, 0),
                   axis=1).astype(dtype)                            # (S,)
    cur = counts_at.astype(dtype)                                   # (S, N)
    minc_b = minc[:, None]
    maxc_b = maxc[:, None]
    even = jnp.where(
        cur != minc_b,
        jnp.where(minc_b == 0.0, -1.0,
                  (minc_b - cur) / jnp.where(minc_b == 0.0, 1.0, minc_b)),
        jnp.where(minc_b == maxc_b, -1.0,
                  jnp.where(minc_b == 0.0, 1.0,
                            (maxc_b - minc_b) / jnp.where(minc_b == 0.0, 1.0, minc_b))),
    )
    # empty property set -> boost 0 (spread.go evenSpreadScoreBoost early
    # return), but the missing-attribute -1.0 penalty applies regardless
    # (SpreadScorer.score checks `ok` before consulting the property set)
    even = jnp.where(any_present[:, None], even, 0.0)
    even = jnp.where(spread_val_ok, even, -1.0)

    boost = jnp.where(spread_has_targets[:, None], explicit, even)  # (S, N)
    # fixed-tree reduction: spread_total feeds the != 0 presence test,
    # so its float add order must not vary with the fusion context
    return _pairwise_sum_xp(jnp, boost), boost                      # (N,)


def _permute_node_axis(tie_perm, available, used0, placed_tg0, placed_job0,
                       feasible, affinity_boost, dev_affinity,
                       spread_val_id, spread_val_ok, dp_val_id, dp_val_ok):
    """Gather every per-node array into tie-break-permuted space — the
    single definition shared by the per-placement loop and the bulk
    solver, so a new per-node input can't be permuted in one and
    forgotten in the other."""
    return (available[tie_perm], used0[tie_perm], placed_tg0[tie_perm],
            placed_job0[tie_perm], feasible[tie_perm],
            affinity_boost[tie_perm], dev_affinity[tie_perm],
            spread_val_id[:, tie_perm], spread_val_ok[:, tie_perm],
            dp_val_id[:, tie_perm] if dp_val_id.shape[0] else dp_val_id,
            dp_val_ok[:, tie_perm] if dp_val_ok.shape[0] else dp_val_ok)


def _scan_steps_xp(xp, active):
    """Steps the placement loop runs for an `active` column: up to and
    including its last active row, 0 if none. One rule for the kernel
    (jnp) and for the host's counters (numpy)."""
    k = active.shape[0]
    return xp.max(xp.where(active, xp.arange(1, k + 1), 0), initial=0)


def scan_steps(active) -> int:
    """Host twin of the bound solve_task_group computes on the device."""
    import numpy as np

    return int(_scan_steps_xp(np, np.asarray(active, dtype=bool)))


@partial(jax.jit, donate_argnums=())
def solve_task_group(
    available,         # (N, D)
    used0,             # (N, D)
    placed_tg0,        # (N,)  int32
    placed_job0,       # (N,)  int32
    ask,               # (D,)
    feasible,          # (N,)  bool
    affinity_boost,    # (N,)
    dev_affinity,      # (N,)
    penalty_idx,       # (K,)  int32, -1 = none
    active,            # (K,)  bool (False = padding step)
    spread_val_id,     # (S, N) int32
    spread_val_ok,     # (S, N) bool
    spread_counts0,    # (S, V) int32
    spread_desired,    # (S, V)
    spread_has_targets,  # (S,) bool
    spread_weight,     # (S,)
    dp_val_id,         # (P, N) int32
    dp_val_ok,         # (P, N) bool
    dp_counts0,        # (P, Vd) int32
    dp_limit,          # (P,)
    lowest_boost0,     # ()
    tg_count,          # ()
    dh_job,            # () bool
    dh_tg,             # () bool
    spread_alg,        # () bool
    tie_perm=None,     # (N,) int32 permutation: tie-break priority order
):
    """Place K allocations of one task group. Returns per-step
    (choice, found, score): the chosen node index, whether any node fit,
    and the winning normalized score.

    A device loop of as many steps as placements are asked: K is the
    padded length one compiled program serves, and the loop stops after
    the last active row (scan_steps), so rows past it place nothing and
    cost nothing; they read choice 0, found False, score NEG. An
    inactive row before that bound runs its step and masks `found`.

    The loop's carry is the proposed cluster state — usage, per-node
    placement counts, spread value counts, distinct_property value
    counts — exactly the state the host path threads through
    ctx.proposed_allocs + SpreadScorer + propertyset between placements
    (generic_sched.go:511-600 commit loop). Beside the spread's (S, V)
    table it carries the count of each node's own value, (S, N) (and
    (P, N) for distinct_property, whose table it then does not need): a
    placement adds one to the nodes that share the chosen node's value,
    where reading the table at every node every step is, on the TPU, a
    chain of compare-selects over V that was most of a step's time.

    tie_perm replaces the host path's per-eval node shuffle (reference
    scheduler/util.go:167 shuffleNodes): the whole solve runs in
    PERMUTED node space (one up-front gather of every per-node array, so
    the loop body stays a plain argmax) and choices map back through the
    permutation at the end. Equal-scoring winners follow the
    permutation's priority order — racing workers diverge on ties
    without reordering the (cached, canonical) per-node arrays
    host-side.
    """
    p = dp_val_id.shape[0]
    n = available.shape[0]
    k = active.shape[0]
    if tie_perm is not None:
        (available, used0, placed_tg0, placed_job0, feasible,
         affinity_boost, dev_affinity, spread_val_id, spread_val_ok,
         dp_val_id, dp_val_ok) = _permute_node_axis(
            tie_perm, available, used0, placed_tg0, placed_job0, feasible,
            affinity_boost, dev_affinity, spread_val_id, spread_val_ok,
            dp_val_id, dp_val_ok)
        inv = jnp.zeros(n, jnp.int32).at[tie_perm].set(
            jnp.arange(n, dtype=jnp.int32))
        penalty_idx = jnp.where(penalty_idx >= 0, inv[penalty_idx], -1)

    n_steps = _scan_steps_xp(jnp, active)
    desired_at = jnp.take_along_axis(spread_desired, spread_val_id, axis=1)

    def score_step(state, pen_idx):
        used, ptg, pjob, scnt, scnt_at, dp_at, lowest = state
        return score_nodes(
            available=available, used=used, ask=ask, feasible=feasible,
            placed_tg=ptg, placed_job=pjob, affinity_boost=affinity_boost,
            dev_affinity=dev_affinity, penalty_idx=pen_idx,
            spread_val_id=spread_val_id, spread_val_ok=spread_val_ok,
            spread_counts=scnt, spread_desired=spread_desired,
            spread_has_targets=spread_has_targets, spread_weight=spread_weight,
            dp_val_id=dp_val_id, dp_val_ok=dp_val_ok, dp_counts=dp_counts0,
            dp_limit=dp_limit,
            lowest_boost=lowest, tg_count=tg_count,
            dh_job=dh_job, dh_tg=dh_tg, spread_alg=spread_alg,
            spread_counts_at=scnt_at, spread_desired_at=desired_at,
            dp_counts_at=dp_at,
        )

    def step(i, carry):
        state, choices, scores = carry
        used, ptg, pjob, scnt, scnt_at, dp_at, lowest = state
        score, _, boost = score_step(state, penalty_idx[i])

        with jax.named_scope("select"):
            choice = jnp.argmax(score).astype(choices.dtype)
            found = active[i] & (score[choice] > NEG)

        with jax.named_scope("usage_update"):
            onehot = (jnp.arange(n) == choice) & found
            used = used + ask[None, :] * onehot[:, None]
            ptg = ptg + onehot.astype(ptg.dtype)
            pjob = pjob + onehot.astype(pjob.dtype)

            sel_ok = spread_val_ok[:, choice] & found              # (S,)
            sel_val = spread_val_id[:, choice]                      # (S,)
            scnt = scnt + ((jnp.arange(scnt.shape[1]) == sel_val[:, None])
                           & sel_ok[:, None]).astype(scnt.dtype)
            scnt_at = scnt_at + ((spread_val_id == sel_val[:, None])
                                 & sel_ok[:, None]).astype(scnt_at.dtype)

            if p:
                dsel_ok = dp_val_ok[:, choice] & found             # (P,)
                dsel_val = dp_val_id[:, choice]                    # (P,)
                dp_at = dp_at + ((dp_val_id == dsel_val[:, None])
                                 & dsel_ok[:, None]).astype(dp_at.dtype)

            # SpreadIterator tracks the lowest explicit boost it has
            # handed out (spread.go lowestBoost); we update it with the
            # chosen node's explicit boosts
            chosen_boost = jnp.where(spread_has_targets & sel_ok,
                                     boost[:, choice], jnp.inf)
            lowest = jnp.minimum(lowest,
                                 jnp.min(chosen_boost, initial=jnp.inf))

        return ((used, ptg, pjob, scnt, scnt_at, dp_at, lowest),
                choices.at[i].set(choice), scores.at[i].set(score[choice]))

    init = (used0, placed_tg0, placed_job0, spread_counts0,
            jnp.take_along_axis(spread_counts0, spread_val_id, axis=1),
            jnp.take_along_axis(dp_counts0, dp_val_id, axis=1),
            lowest_boost0)
    score_dtype = jax.eval_shape(score_step, init, penalty_idx[0])[0].dtype
    _, choices, scores = jax.lax.fori_loop(
        0, n_steps, step,
        (init, jnp.zeros(k, jnp.int32), jnp.full(k, NEG, score_dtype)))
    # a step found a node iff its row is active and its best score is one
    founds = active & (scores > NEG)
    if tie_perm is not None:
        # a row past the bound keeps choice 0, not tie_perm[0]
        choices = jnp.where(jnp.arange(k) < n_steps, tie_perm[choices], 0)
    return choices, founds, scores


# ---------------------------------------------------------------------------
# fused transfer layout
# ---------------------------------------------------------------------------
#
# A small solve is bound by its launch's fixed dispatch + readback cost,
# not by FLOPs, so the fused entry point takes the 20 logical arguments
# as 9 arrays (usage and 8 packed ones) and returns one packed output:
# a whole task-group solve costs two upload batches, one of them off
# the critical path, and one readback. How much that saves was
# judged in an earlier environment; not measured on the current chip
# (ROADMAP D3).
#
# The arguments are split by who can change them. `used` is the one
# input a racing evaluation moves (through the in-flight overlay and
# the feed's base), so it travels alone and is gathered under the
# placer's lock; everything pack_solve_args packs is fixed once the
# task group's tensors are built and is on the device before the lock
# is taken (placer.stage).
#
# used (N, D): proposed usage, f32
# node_mat (N, D+6): avail[D] | placed_tg | placed_job | feasible
#                    | affinity | dev_affinity | tie_perm
# step_mat (K, 2):  penalty_idx | active
# spread_node (2S, N): val_id rows then val_ok rows
# spread_tab (2S, V):  counts rows then desired rows
# spread_meta (S, 2):  has_targets | weight
# dp_node (2P, N): val_id rows then val_ok rows
# dp_tab (P, Vd+1): counts columns | limit column
# scalars (5+D,): lowest_boost | tg_count | dh_job | dh_tg | spread_alg | ask[D]


def pack_solve_args(available, placed_tg0, placed_job0, ask, feasible,
                    affinity_boost, penalty_idx, active, spread_val_id,
                    spread_val_ok, spread_counts0, spread_desired,
                    spread_has_targets, spread_weight, lowest_boost0,
                    tg_count, dh_job, dh_tg, spread_alg,
                    dev_affinity=None, dp_val_id=None, dp_val_ok=None,
                    dp_counts0=None, dp_limit=None, tie_perm=None):
    """Host-side packing (numpy) of solve_task_group_fused's static
    arguments: all of them but `used`."""
    import numpy as np

    f = np.float32
    n = np.asarray(available).shape[0]
    if dev_affinity is None:
        dev_affinity = np.zeros(n, f)
    if tie_perm is None:
        tie_perm = np.arange(n)
    node_mat = np.concatenate([
        np.asarray(available, f),
        np.asarray(placed_tg0, f)[:, None], np.asarray(placed_job0, f)[:, None],
        np.asarray(feasible, f)[:, None], np.asarray(affinity_boost, f)[:, None],
        np.asarray(dev_affinity, f)[:, None], np.asarray(tie_perm, f)[:, None],
    ], axis=1)
    step_mat = np.stack([np.asarray(penalty_idx, f),
                         np.asarray(active, f)], axis=1)
    spread_node = np.concatenate([np.asarray(spread_val_id, f),
                                  np.asarray(spread_val_ok, f)], axis=0)
    spread_tab = np.concatenate([np.asarray(spread_counts0, f),
                                 np.asarray(spread_desired, f)], axis=0)
    spread_meta = np.stack([np.asarray(spread_has_targets, f),
                            np.asarray(spread_weight, f)], axis=1) \
        if len(spread_weight) else np.zeros((0, 2), f)
    if dp_val_id is None or not len(dp_val_id):
        dp_node = np.zeros((0, n), f)
        dp_tab = np.zeros((0, 2), f)
    else:
        dp_node = np.concatenate([np.asarray(dp_val_id, f),
                                  np.asarray(dp_val_ok, f)], axis=0)
        dp_tab = np.concatenate([np.asarray(dp_counts0, f),
                                 np.asarray(dp_limit, f)[:, None]], axis=1)
    scalars = np.concatenate([
        np.array([lowest_boost0, tg_count, dh_job, dh_tg, spread_alg], f),
        np.asarray(ask, f)])
    return (node_mat, step_mat, spread_node, spread_tab, spread_meta,
            dp_node, dp_tab, scalars)


@jax.jit
def solve_task_group_fused(used, node_mat, step_mat, spread_node, spread_tab,
                           spread_meta, dp_node, dp_tab, scalars):
    """Transfer-fused solve: unpack on device, run the same loop, return
    one (3, K) array of [choice, found, score] rows."""
    s = spread_meta.shape[0]
    p = dp_node.shape[0] // 2
    d = used.shape[1]
    choices, founds, scores = solve_task_group(
        node_mat[:, 0:d], used,
        node_mat[:, d].astype(jnp.int32),
        node_mat[:, d + 1].astype(jnp.int32),
        scalars[5:5 + d], node_mat[:, d + 2] > 0.5, node_mat[:, d + 3],
        node_mat[:, d + 4],
        step_mat[:, 0].astype(jnp.int32), step_mat[:, 1] > 0.5,
        spread_node[:s].astype(jnp.int32), spread_node[s:] > 0.5,
        spread_tab[:s].astype(jnp.int32), spread_tab[s:],
        spread_meta[:, 0] > 0.5, spread_meta[:, 1],
        dp_node[:p].astype(jnp.int32), dp_node[p:] > 0.5,
        dp_tab[:, :-1].astype(jnp.int32), dp_tab[:, -1],
        scalars[0], scalars[1], scalars[2] > 0.5, scalars[3] > 0.5,
        scalars[4] > 0.5,
        node_mat[:, d + 5].astype(jnp.int32),
    )
    return jnp.stack([choices.astype(scores.dtype),
                      founds.astype(scores.dtype), scores])


# ---------------------------------------------------------------------------
# bulk solve: K identical placements as counts, O(K/B) sequential steps
# ---------------------------------------------------------------------------
#
# The C2M engine. A fresh job's task group asks for K identical
# placements; the per-placement loop costs K sequential steps of some
# twenty small device ops each (15 us a step at 16,384 nodes on a v5e,
# PERF.md section 5: their count sets it, not the bytes a step
# touches). This solver instead
# assigns a BATCH of B placements per step: score all nodes once
# (identical math to score_nodes), then give the best-scoring nodes
# their fill in score order — per-node capacity for binpack (the greedy
# winner keeps winning until full, so fill-to-capacity IS the greedy
# trajectory), one per node per step for spread (approximating the
# round-robin; parity is measured, not assumed). Counts, not choices,
# come back: one (N,) readback regardless of K. This is the
# "batched feasibility masking + scoring + assignment" shape BASELINE.md
# names as the north-star design.


def _bulk_scan(
    available,         # (N, D)
    used0,             # (N, D)
    ask,               # (D,)
    feasible,          # (N,) bool
    placed_tg0,        # (N,) int32
    placed_job0,       # (N,) int32
    affinity_boost,    # (N,)
    dev_affinity,      # (N,)
    spread_val_id,     # (S, N) int32
    spread_val_ok,     # (S, N) bool
    spread_counts0,    # (S, V) int32
    spread_desired,    # (S, V)
    spread_has_targets,  # (S,) bool
    spread_weight,     # (S,)
    k_total,           # () int32 placements wanted
    tg_count,          # ()
    dh_job,            # () bool
    dh_tg,             # () bool
    spread_alg,        # () bool
    tie_perm,          # (N,) int32
    *,
    batch: int,        # placements per step
    n_steps: int,      # static scan length >= ceil(k_total / batch)
):
    """-> (N,) int32 per-node placement counts in canonical order —
    ONE readback regardless of K. Runs in permuted node space like
    solve_task_group; counts map back at the end. (The trajectory's
    mean score is recomputed host-side by _bulk_trajectory_mean — the
    step-start scores here under-report a fill-to-capacity batch.)"""
    n = available.shape[0]
    s = spread_val_id.shape[0]
    dp_val_id = jnp.zeros((0, n), jnp.int32)
    dp_val_ok = jnp.zeros((0, n), bool)
    dp_counts = jnp.zeros((0, 1), jnp.int32)
    dp_limit = jnp.zeros(0)
    (available, used0, placed_tg0, placed_job0, feasible,
     affinity_boost, dev_affinity, spread_val_id, spread_val_ok,
     dp_val_id, dp_val_ok) = _permute_node_axis(
        tie_perm, available, used0, placed_tg0, placed_job0, feasible,
        affinity_boost, dev_affinity, spread_val_id, spread_val_ok,
        dp_val_id, dp_val_ok)

    # per-node max one placement under distinct_hosts; else fill for
    # binpack, one-per-step for spread (WorstFit drops a node's score
    # after each placement, so greedy round-robins)
    single = dh_job | dh_tg | spread_alg

    ask_pos = ask > 0

    def step(carry, _):
        used, ptg, pjob, scnt, taken, remaining = carry
        score, _, _ = score_nodes(
            available=available, used=used, ask=ask, feasible=feasible,
            placed_tg=ptg, placed_job=pjob, affinity_boost=affinity_boost,
            dev_affinity=dev_affinity, penalty_idx=jnp.int32(-1),
            spread_val_id=spread_val_id, spread_val_ok=spread_val_ok,
            spread_counts=scnt, spread_desired=spread_desired,
            spread_has_targets=spread_has_targets, spread_weight=spread_weight,
            dp_val_id=dp_val_id, dp_val_ok=dp_val_ok, dp_counts=dp_counts,
            dp_limit=dp_limit,
            lowest_boost=-1.0, tg_count=tg_count,
            dh_job=dh_job, dh_tg=dh_tg, spread_alg=spread_alg,
        )
        budget = jnp.minimum(remaining, batch)
        # how many MORE fit on each node; a zero ask in every dimension
        # means infinite per-node capacity, so clamp to the step budget
        # BEFORE the int32 cast (inf -> INT32_MAX would overflow the
        # cumsum below)
        free = available - used
        per_dim = jnp.where(ask_pos[None, :], jnp.floor(free / jnp.where(
            ask_pos, ask, 1.0)[None, :]), jnp.inf)
        cap = jnp.min(per_dim, axis=1)
        cap = jnp.clip(cap, 0, None)
        cap = jnp.where(score > NEG, cap, 0.0)
        cap = jnp.where(single, jnp.minimum(cap, 1.0), cap)
        cap = jnp.minimum(cap, budget.astype(cap.dtype)).astype(jnp.int32)
        order = jnp.argsort(-score)               # stable: ties by index
        cap_sorted = cap[order]
        cum = jnp.cumsum(cap_sorted)
        take_sorted = jnp.clip(budget - (cum - cap_sorted), 0, cap_sorted)
        take = jnp.zeros(n, jnp.int32).at[order].set(take_sorted)

        used = used + ask[None, :] * take[:, None].astype(used.dtype)
        ptg = ptg + take
        pjob = pjob + take
        if s:
            scnt = scnt.at[jnp.arange(s)[:, None], spread_val_id].add(
                jnp.where(spread_val_ok, take[None, :], 0))
        placed_now = jnp.sum(take).astype(jnp.int32)
        return (used, ptg, pjob, scnt, taken + take,
                remaining - placed_now), None

    init = (used0, placed_tg0, placed_job0, spread_counts0,
            jnp.zeros(n, jnp.int32), jnp.int32(k_total))
    (used, ptg, pjob, scnt, taken, remaining), _ = jax.lax.scan(
        init=init, f=step, xs=None, length=n_steps)
    return jnp.zeros(n, jnp.int32).at[tie_perm].set(taken)


solve_bulk = partial(jax.jit, static_argnames=("batch", "n_steps"))(_bulk_scan)


@partial(jax.jit, static_argnames=("batch", "n_steps"))
def solve_bulk_fused(
    available,   # (N, D) — device-RESIDENT per node-set version
    feasible,    # (N,) bool — resident per task-group mask signature
    aff,         # (N,) — resident per affinity signature
    dyn,         # (N, D+2) float32: used | placed_tg | placed_job (per eval)
    ask,         # (D,)
    k_total,     # () int32
    tg_count,    # () float
    seed,        # () uint32: tie-break permutation PRNG seed
    *,
    batch: int,
    n_steps: int,
):
    """Transfer-minimal bulk solve: the big static arrays live on the
    device across evals (see the fused-transfer note above); each eval
    ships one (N, D+2) f32 matrix + a handful of scalars, and the
    tie-break permutation is generated ON DEVICE from the seed. No spread/dh/dp tables by bulk
    eligibility (placer._bulk_eligible)."""
    n, d = available.shape
    tie_perm = jax.random.permutation(
        jax.random.PRNGKey(seed), n).astype(jnp.int32)
    f = available.dtype
    return _bulk_scan(
        available, dyn[:, :d].astype(f), ask.astype(f), feasible,
        dyn[:, d].astype(jnp.int32), dyn[:, d + 1].astype(jnp.int32),
        aff.astype(f), jnp.zeros(n, f),
        jnp.zeros((0, n), jnp.int32), jnp.zeros((0, n), bool),
        jnp.zeros((0, 1), jnp.int32), jnp.zeros((0, 1), f),
        jnp.zeros(0, bool), jnp.zeros(0, f),
        k_total, tg_count, False, False, False, tie_perm,
        batch=batch, n_steps=n_steps)


@partial(jax.jit, static_argnames=())
def score_nodes_once(
    available, used, ask, feasible, placed_tg, placed_job, affinity_boost,
    penalty_idx, spread_val_id, spread_val_ok, spread_counts, spread_desired,
    spread_has_targets, spread_weight, lowest_boost, tg_count, dh_job, dh_tg,
    spread_alg, dev_affinity=None, dp_val_id=None, dp_val_ok=None,
    dp_counts=None, dp_limit=None,
):
    """Single-placement score vector — the differential-test surface
    pinned against the host oracle scheduler.rank.score_nodes."""
    n = available.shape[0]
    if dev_affinity is None:
        dev_affinity = jnp.zeros(n)
    if dp_val_id is None:
        dp_val_id = jnp.zeros((0, n), jnp.int32)
        dp_val_ok = jnp.zeros((0, n), bool)
        dp_counts = jnp.zeros((0, 1), jnp.int32)
        dp_limit = jnp.zeros(0)
    score, _, _ = score_nodes(
        available=available, used=used, ask=ask, feasible=feasible,
        placed_tg=placed_tg, placed_job=placed_job,
        affinity_boost=affinity_boost, dev_affinity=dev_affinity,
        penalty_idx=penalty_idx,
        spread_val_id=spread_val_id, spread_val_ok=spread_val_ok,
        spread_counts=spread_counts, spread_desired=spread_desired,
        spread_has_targets=spread_has_targets, spread_weight=spread_weight,
        dp_val_id=dp_val_id, dp_val_ok=dp_val_ok, dp_counts=dp_counts,
        dp_limit=dp_limit,
        lowest_boost=lowest_boost, tg_count=tg_count,
        dh_job=dh_job, dh_tg=dh_tg, spread_alg=spread_alg,
    )
    return score


def _solve_bulk_multi_impl(
    used0,       # (N, D) f32 usage carry — device-RESIDENT, donated back
    available,   # (N, D) f32 resident capacity
    feas,        # (G, N) bool stacked per-eval feasibility masks
    aff,         # (G, N) f32 stacked per-eval affinity boosts
    ask,         # (G, D) f32 per-eval resource asks
    k,           # (G,) int32 placements wanted per eval
    tg_count,    # (G,) f32 (kept for signature parity; scores are
                 #          recomputed host-side for the trajectory mean)
    seeds,       # (G,) uint32 per-eval tie-break seeds
    cidx,        # (C,) int32 usage-correction node rows (0 = no-op slot)
    cdelta,      # (C, D) f32 usage-correction deltas added to used0
                 #        before solving (rejected-placement phantoms
                 #        arrive negative; see tensor/solver.py ledger)
    *,
    g: int,
):
    """Chained bulk solves for G independent fresh-placement evals in ONE
    launch -> ((N, D) new usage carry staying on device, (G, N) int16
    per-node counts — the only readback).

    Every launch pays a fixed dispatch + readback cost, so per-eval
    launches cap the whole pipeline; here the usage state never leaves
    the device between launches and that cost amortizes over G evals. Eval i places
    k[i] allocations of ask[i] by BestFit fill-to-capacity against the
    usage state left by eval i-1, with tie-breaks from a per-eval
    on-device permutation of seeds[i] (same PRNG as solve_bulk_fused).

    ONE fill pass per eval, not a scan of score-refresh steps: a node's
    BestFit score depends only on its own usage, so filling the best
    node to capacity never re-orders the remaining nodes — the one-pass
    sorted fill IS the re-scored greedy trajectory (the refresh steps of
    _bulk_scan only repeat the score + full-sort work, ~12ms of device
    time per step at 10K nodes). The in-eval anti-affinity term is
    dropped for the same reason the trajectory tolerates it in
    _bulk_scan: under fill-to-capacity every chosen node saturates its
    capacity regardless of score magnitude, so the anti term can only
    affect reported scores (recomputed host-side), not choices, except
    through order among non-equal nodes — bounded by the same score
    parity the bulk path is benched against. No statics besides G, so
    the jit cache holds exactly two graph variants (G=1, G=G_PAD)."""
    n, d = available.shape
    f = available.dtype
    # fold queued usage corrections into the carry (scatter-add; the
    # clamp guards against a correction racing a concurrent resync)
    used0 = jnp.maximum(used0.at[cidx].add(cdelta), 0.0)
    # Tie-breaks: a per-(eval, node) additive score jitter << any
    # meaningful score gap replaces the old permutation+stable-argsort
    # scheme. Same decorrelation of racing workers' choices among
    # equal-scoring nodes, but the sort key becomes a plain float —
    # which is what lets the SHARDED twin of this kernel
    # (tensor/sharding.make_solve_bulk_multi_sharded) use per-shard
    # top-k + a small gathered merge instead of a replicated full sort.
    jits = jax.vmap(
        lambda s: jax.random.uniform(jax.random.PRNGKey(s), (n,),
                                     jnp.float32, 0.0, TIE_JITTER)
    )(seeds)                                                       # (G, N)

    def one_eval(used, gi):
        # named scopes: the evaluation's phases in the profiler's trace
        ask_g = ask[gi]
        ask_pos = ask_g > 0
        new_used = used + ask_g[None, :]
        with jax.named_scope("feasibility"):
            ok = feas[gi] & jnp.all(new_used <= available, axis=1)
        with jax.named_scope("score"):
            fitness = fit_scores(available, new_used, False)
            aff_g = aff[gi]
            aff_present = aff_g != 0.0
            divisor = 1.0 + aff_present.astype(f)
            score = (fitness + jnp.where(aff_present, aff_g, 0.0)) / divisor
            score = jnp.where(ok, score, NEG)

        with jax.named_scope("capacity"):
            free = available - used
            per_dim = jnp.where(
                ask_pos[None, :],
                jnp.floor(free / jnp.where(ask_pos, ask_g, 1.0)[None, :]),
                jnp.inf)
            cap = jnp.clip(jnp.min(per_dim, axis=1), 0, None)
            cap = jnp.where(score > NEG, cap, 0.0)
            budget = k[gi]
            cap = jnp.minimum(cap, budget.astype(cap.dtype)).astype(jnp.int32)
        with jax.named_scope("sorted_fill"):
            key = score + jits[gi]
            order = jnp.argsort(-key)            # residual ties: node index
            cap_sorted = cap[order]
            cum = jnp.cumsum(cap_sorted)
            take_sorted = jnp.clip(budget - (cum - cap_sorted), 0,
                                   cap_sorted)
            take = jnp.zeros(n, jnp.int32).at[order].set(take_sorted)
        with jax.named_scope("usage_update"):
            used = used + ask_g[None, :] * take[:, None].astype(used.dtype)
        return used, take.astype(jnp.int16)

    used, counts = jax.lax.scan(one_eval, used0, jnp.arange(g))
    return used, counts


# public jitted entry; the raw impl stays importable so the batch solver
# (tensor/batch_solver.solve_batch) can inline the exact greedy chain as
# its baseline arm inside ONE launch instead of a second round trip
solve_bulk_multi = partial(jax.jit, static_argnames=("g",),
                           donate_argnums=(0,))(_solve_bulk_multi_impl)


@jax.jit
def preempt_pick(
    available,   # (N, D) capacity
    used0,       # (N, D) proposed usage
    evictable0,  # (N, D) sum of preemptible lower-priority alloc usage
    ask,         # (D,)
    feasible,    # (N,) bool constraint/driver mask
    net_prio,    # (N,) approximate netPriority of the node's preemptible
                 #      set: max + sum/max (reference rank.go netPriority
                 #      over the victim set; the per-node aggregate is an
                 #      upper bound used only to ORDER candidate nodes —
                 #      the host recomputes the exact score for the
                 #      chosen node's actual victims)
    active,      # (K,) bool request slots
):
    """Batched preemption node choice for K requests -> (K,) int32 node
    index per request (-1 = no preemptible node). Mirrors the host
    fallback's node ordering: fit score after eviction + the logistic
    preemption penalty (rank.go:894 preemptionScore), averaged like
    ScoreNormalizationIterator. The scan carries usage and remaining
    evictable capacity so sibling requests don't pile onto one node;
    exact victim selection stays host-side per chosen node
    (scheduler/preemption.py)."""
    f = available.dtype
    rate, origin = 0.0048, 2048.0
    pscore_node = 1.0 / (1.0 + jnp.exp(rate * (net_prio - origin)))

    def step(carry, i):
        used, evictable = carry
        new_used = used + ask[None, :]
        deficit = jnp.maximum(new_used - available, 0.0)
        can = feasible & jnp.all(deficit <= evictable, axis=1)
        needs_evict = jnp.any(deficit > 0.0, axis=1)
        fitness = fit_scores(available, jnp.minimum(new_used, available), False)
        divisor = 1.0 + needs_evict.astype(f)
        score = (fitness + jnp.where(needs_evict, pscore_node, 0.0)) / divisor
        score = jnp.where(can, score, NEG)
        best = jnp.argmax(score)
        found = (score[best] > NEG) & active[i]

        def apply(c):
            used, evictable = c
            used = used.at[best].set(
                jnp.minimum(used[best] + ask, available[best]))
            evictable = evictable.at[best].set(
                jnp.maximum(evictable[best] - deficit[best], 0.0))
            return used, evictable

        used, evictable = jax.lax.cond(found, apply, lambda c: c,
                                       (used, evictable))
        return (used, evictable), jnp.where(found, best, -1)

    _, picks = jax.lax.scan(step, (used0, evictable0),
                            jnp.arange(active.shape[0]))
    return picks.astype(jnp.int32)


@jax.jit
def preempt_solve(
    available,   # (N, D) capacity
    used0,       # (N, D) proposed usage
    ask,         # (D,)
    feasible,    # (N,) bool constraint/driver mask
    net_prio,    # (N,) approximate netPriority aggregate (see preempt_pick)
    active,      # (K,) bool request slots
    v_prio,      # (N, V) f32 victim priorities (column order: priority
                 #        asc, alloc id asc — scheduler.preemption.
                 #        victim_candidates' canonical order)
    v_vec,       # (N, V, D) f32 victim allocated resource vectors
    v_elig,      # (N, V) bool eligibility (delta-10 + usage-counting)
    v_flag,      # (N, V) bool port/device holders the dense columns
                 #        can't model — rows selecting one are flagged
                 #        for the exact host scanner
):
    """Whole preemption solve for K requests in ONE launch: node choice
    (same ordering as preempt_pick — fit after eviction + logistic
    preemption penalty) AND concrete victim selection.

    Victims are a priority-ascending PREFIX of the chosen node's
    still-unclaimed eligible column, taken until the deficit is covered
    in every resource dim (the kernel analog of preempt_for_task_group's
    ascending priority groups; within-group distance refinement and the
    filterSuperset drop stay host-side in the exact scanner, which is
    the fallback for flagged rows). The carry commits usage, remaining
    evictable capacity, and a per-victim `taken` mask so sibling
    requests in the same launch never double-claim a victim.

    Returns (picks (K,) i32 node or -1,
             victims (K, V) bool mask into the picked node's column,
             flagged (K,) bool — victim set includes an exact-resource
                     holder, route this row through the host scanner,
             scores (K,) f32 winning node score).
    """
    f = available.dtype
    rate, origin = 0.0048, 2048.0
    pscore_node = 1.0 / (1.0 + jnp.exp(rate * (net_prio - origin)))

    ev0 = jnp.sum(v_vec * v_elig[:, :, None].astype(f), axis=1)
    taken0 = jnp.zeros(v_prio.shape, dtype=bool)

    def step(carry, i):
        used, ev, taken = carry
        new_used = used + ask[None, :]
        deficit = jnp.maximum(new_used - available, 0.0)
        can = feasible & jnp.all(deficit <= ev, axis=1)
        needs_evict = jnp.any(deficit > 0.0, axis=1)
        fitness = fit_scores(available, jnp.minimum(new_used, available), False)
        divisor = 1.0 + needs_evict.astype(f)
        score = (fitness + jnp.where(needs_evict, pscore_node, 0.0)) / divisor
        score = jnp.where(can, score, NEG)
        best = jnp.argmax(score)
        found = (score[best] > NEG) & active[i]

        # priority-ascending prefix over the best node's unclaimed
        # eligible column: a victim is selected while ANY dim's deficit
        # is not yet covered by the victims before it (columns are
        # pre-sorted, so cumsum-before IS the prefix sum)
        row_elig = v_elig[best] & ~taken[best]
        vecs = v_vec[best] * row_elig[:, None].astype(f)
        cum_before = jnp.cumsum(vecs, axis=0) - vecs
        def_b = deficit[best]
        sel = (row_elig & needs_evict[best]
               & jnp.any((def_b[None, :] > 0.0)
                         & (cum_before < def_b[None, :]), axis=1))
        sel = sel & found
        evicted = jnp.sum(v_vec[best] * sel[:, None].astype(f), axis=0)
        flagged_i = jnp.any(sel & v_flag[best])

        def apply(c):
            used, ev, taken = c
            used = used.at[best].set(
                jnp.maximum(used[best] + ask - evicted, 0.0))
            ev = ev.at[best].set(jnp.maximum(ev[best] - evicted, 0.0))
            taken = taken.at[best].set(taken[best] | sel)
            return used, ev, taken

        used, ev, taken = jax.lax.cond(found, apply, lambda c: c,
                                       (used, ev, taken))
        return ((used, ev, taken),
                (jnp.where(found, best, -1), sel, flagged_i,
                 jnp.where(found, score[best], NEG)))

    _, (picks, victims, flagged, scores) = jax.lax.scan(
        step, (used0, ev0, taken0), jnp.arange(active.shape[0]))
    return (picks.astype(jnp.int32), victims, flagged, scores.astype(f))
