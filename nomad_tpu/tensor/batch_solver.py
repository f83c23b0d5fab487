"""Global-batch assignment solver behind SchedulerAlgorithm="tpu-solve".

PR 5's `EvalBroker.dequeue_batch` hands each worker a fused batch of
evals sharing one snapshot, but the greedy tier still places them one
scan step at a time in arrival order — the batch's cross-eval packing
quality is left on the table. This module solves the whole batch as ONE
tensorized assignment problem (CvxCluster, arxiv 2605.01614: granular
allocation as one iterative formulation; arxiv 2511.08373: global
formulations dominate greedy on bin-pack quality):

  * build the (G, N) feasibility-mask x score matrix for every
    placement request across every eval in the batch (the same
    tensor/cluster.py builds and kernels.fit_scores the greedy tier
    uses — satellite-deduped so the two tiers cannot drift),
  * run iterative AUCTION rounds inside one jitted while_loop: each
    still-unsatisfied eval bids for its TOP-R nodes by score; per-node
    capacity conflicts are resolved by a price update on contested
    nodes (losers are pushed to their next-best nodes on the following
    round); each node's winning eval fills its won nodes to capacity
    in score order until its demand runs out; usage tensors are
    updated once per ROUND instead of once per alloc,
  * run the sequential greedy chain (`kernels._solve_bulk_multi_impl`,
    the exact "tpu-binpack" math) in the SAME launch and keep whichever
    whole-batch assignment scores better — so `tpu-solve` dominates the
    greedy tier on packing quality by construction, and the greedy arm
    doubles as the in-kernel fallback when the auction leaves demand
    unplaced (capacity-fragmented instances).

Convergence: every round the globally best (eval, node) bid wins its
node and places at least one allocation (its feasibility check already
proved one unit fits), so total remaining demand strictly decreases
while any request is placeable; the loop exits on MAX_ROUNDS, on zero
remaining demand, or on a fully stalled round. Measured
rounds-to-convergence on the bench shapes is in PERF.md
("Global-batch solve").

The packing-quality metric is order-independent on purpose: the score
of an assignment is sum over nodes of (allocs placed on the node) x
(final-state BestFit fitness of the node). Scoring the FINAL usage
state rewards consolidation without depending on the order placements
were made in — both arms of the portfolio are scored on the same
footing, and `packing_score_np` is the same formula the tests and the
bench recompute host-side.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import (NEG, TIE_JITTER, _fit_scores_xp, _pairwise_sum_xp,
                      _solve_bulk_multi_impl)

# Auction round budget. Each round fills at least one node to capacity
# (see convergence note above); bench batches (G <= 16 evals, 1K-10K
# nodes) converge in well under half this (PERF.md table).
MAX_ROUNDS = 64
# Nodes each request bids for per round. One-node-per-round auctions
# need ~nodes-touched rounds to drain a large demand (measured: the 10K
# rung hit the MAX_ROUNDS cap with demand left over); bidding for the
# top-R nodes at once and letting the winner fill them in score order
# cuts rounds to ~touched/R with the identical conflict rule.
TOP_R = 16
# Price bump applied to a node that received more than one bid in a
# round. Sized like TIE_JITTER: far below any meaningful score gap, so
# prices only re-order requests among near-equal nodes, never force a
# request onto a genuinely worse node ahead of a better free one.
PRICE_EPS = TIE_JITTER
# Restart portfolio: one (jitter_scale, price_temperature) pair per
# auction restart. The tie-break jitter decides which of many
# near-equal packings the auction converges to; restarting with fresh
# jitter and keeping the best-scoring assignment is a randomized
# restart portfolio over those basins. The packing score is pure
# fitness (jitter never enters it), so the max over restarts is a real
# quality improvement, and the auction is the cheap arm of the launch —
# the sequential greedy chain dominates its cost.
#
# The pairs are OFFLINE-FITTED frozen constants, not guesses: scripts/
# fit_portfolio.py replays seeded solver-shaped problems (the obs-plane
# trace shapes: nomad.eval.phase.* + the joint/greedy score pairs the
# Registry already records) and grid-searches (jitter_scale x
# price_temp) for the portfolio with the best auction-vs-greedy win
# rate at EQUAL restart count vs the old fixed five-identical-restarts
# schedule. jitter_scale multiplies the TIE_JITTER range each restart
# draws from (wider = hops basins more aggressively); price_temp
# multiplies PRICE_EPS (hotter = contested nodes repel losers harder,
# colder = bidders keep re-converging on near-full nodes). Entry 0 is
# pinned at (1.0, 1.0) — the legacy basin stays in the portfolio as its
# safety arm, so the fitted portfolio can only add basins, never lose
# the old one. Re-fit with: python scripts/fit_portfolio.py
#
# Fitted 2026-08 over 16 seeded contended problems (64 nodes x 8 evals,
# 55-95% fill): the fit consistently selects COLD price temperatures
# (0.25x) with spread jitter scales — under the BestFit objective the
# losers should keep re-converging on near-full nodes, and basin
# diversity comes from jitter width instead. Mean packing-score edge vs
# greedy improved from -28.59 (legacy five identical restarts) to
# -28.10 at equal restart count; greedy stays ahead on contended
# packings overall, which is exactly why it remains the in-kernel
# safety arm of the portfolio pick below. The duplicate (8.0, 0.25)
# entry is intentional: each slot draws a different fold_in(t) jitter
# stream, so a repeated pair is a fresh sample of its basin.
PORTFOLIO = (
    (1.0, 1.0),   # legacy basin (pinned)
    (8.0, 0.25),
    (0.25, 0.25),
    (4.0, 0.25),
    (8.0, 0.25),
)
RESTARTS = len(PORTFOLIO)


# _pairwise_sum_xp now lives in kernels (score_nodes needs it for the
# spread-presence reduction); re-exported here because sharding.py and
# the PR 14 determinism tests import it from this module.


def _packing_score_xp(xp, counts, available, used_final):
    """Order-independent packing quality of a whole-batch assignment:
    sum_n placed[n] * BestFit-fitness(available[n], used_final[n])."""
    per_node = _fit_scores_xp(xp, available, used_final, False)   # (N,)
    placed = counts.sum(axis=0) if counts.ndim == 2 else counts   # (N,)
    return _pairwise_sum_xp(xp, placed.astype(per_node.dtype) * per_node)


def packing_score_np(counts, available, used_final) -> float:
    """Numpy twin of the in-kernel portfolio metric — used by the
    property tests to score end states."""
    return float(_packing_score_xp(
        np, np.asarray(counts), np.asarray(available, dtype=np.float64),
        np.asarray(used_final, dtype=np.float64)))


def _auction(used0, available, feas, aff, ask, k, jits, g: int, rounds: int,
             top_r: int = TOP_R, price_eps=PRICE_EPS,
             evict=None, pscore=None):
    """One jitted auction: per round each still-unsatisfied request bids
    for its TOP-R nodes by (score + jitter - price); each node accepts
    its best bidder (ties to the lowest eval index) and the winner fills
    its won nodes to capacity in score order until its demand runs out.
    Returns (used, (G, N) int32 take, rounds_run).

    `price_eps` is the per-restart price temperature (PORTFOLIO).
    `evict`/`pscore` thread the preemption victim columns through the
    joint solve: `evict` (N, D) is each node's victim budget — capacity
    reclaimable by evicting its preemptible column (tensor/cluster.
    build_victim_tensors) — and extends the bid/cap feasibility bound to
    available + evict, exhaustion-gated exactly like prices (the budget
    only pays out as `used` crosses `available`; sibling winners see the
    drained budget in the shared usage carry next round). `pscore` (N,)
    is the logistic preemption penalty those over-capacity bids carry
    (rank.go:894), so a preempting placement only beats a free node on
    genuine fit. Both None = the legacy victim-blind auction graph,
    bit-identical to before."""
    n, d = available.shape
    f = available.dtype
    r = min(top_r, n)
    avail_cap = available if evict is None else available + evict
    # int32 throughout the carry: under x64 (tests) arange defaults to
    # int64 and sum() promotes int32 -> int64, which breaks the
    # while_loop's fixed carry types
    g_idx = jnp.arange(g, dtype=jnp.int32)
    ask_pos = ask > 0                                             # (G, D)
    aff_present = aff != 0.0
    divisor = 1.0 + aff_present.astype(f)

    def body(state):
        used, remaining, take, price, rnd, _ = state
        # (G, N) bid matrix against the CURRENT usage state
        new_used = used[None, :, :] + ask[:, None, :]             # (G,N,D)
        ok = feas & jnp.all(new_used <= avail_cap[None, :, :], axis=2)
        ok &= (remaining > 0)[:, None]
        if evict is None:
            fitness = _fit_scores_xp(jnp, available[None, :, :], new_used,
                                     False)                       # (G, N)
            score = (fitness + jnp.where(aff_present, aff, 0.0)) / divisor
        else:
            # over-capacity bids spend victim budget: fitness is scored
            # against true capacity (min-clamped, the preempt_solve
            # convention) and carries the preemption penalty term
            fitness = _fit_scores_xp(
                jnp, available[None, :, :],
                jnp.minimum(new_used, available[None, :, :]), False)
            over = jnp.any(new_used > available[None, :, :], axis=2)
            score = (fitness + jnp.where(aff_present, aff, 0.0)
                     + jnp.where(over, pscore[None, :], 0.0)) / (
                         divisor + over.astype(f))
        bid = jnp.where(ok, score + jits - price[None, :], NEG)
        # each request's R best nodes, descending (top_k is stable:
        # ties go to the lower node index on every layout)
        vals, idxs = jax.lax.top_k(bid, r)                        # (G, R)
        active = vals > NEG / 2
        flat_idx = idxs.reshape(-1)
        flat_val = jnp.where(active, vals, NEG).reshape(-1)
        flat_g = jnp.broadcast_to(g_idx[:, None], (g, r)).reshape(-1)
        # winner per node: highest bid among all surfaced candidates,
        # residual ties to the lowest eval index (deterministic
        # regardless of scatter order)
        node_best = jnp.full(n, NEG, f).at[flat_idx].max(flat_val)
        is_best = (flat_val > NEG / 2) & (flat_val >= node_best[flat_idx])
        node_winner = jnp.full(n, g, jnp.int32).at[flat_idx].min(
            jnp.where(is_best, flat_g, g))
        won = active & (vals >= node_best[idxs]) & (
            node_winner[idxs] == g_idx[:, None])                  # (G, R)
        # capacity of each won node (BestFit fill — the same budget
        # rule as the greedy chain's sorted fill)
        free = avail_cap[idxs] - used[idxs]                       # (G,R,D)
        per_dim = jnp.where(
            ask_pos[:, None, :],
            jnp.floor(free / jnp.where(ask_pos, ask, 1.0)[:, None, :]),
            jnp.inf)
        cap = jnp.clip(jnp.min(per_dim, axis=2), 0, None)
        cap = jnp.where(won, cap, 0.0)                            # (G, R)
        # spend the remaining demand across won nodes in score order
        prefix = jnp.cumsum(cap, axis=1) - cap
        amt = jnp.clip(remaining.astype(cap.dtype)[:, None] - prefix,
                       0.0, cap).astype(jnp.int32)                # (G, R)
        # one scatter per ROUND: won nodes are distinct across all
        # (eval, slot) pairs, losers contribute zero rows
        used = used.at[flat_idx].add(
            (ask[:, None, :] * amt[..., None].astype(f)).reshape(-1, d))
        take = take.at[g_idx[:, None], idxs].add(amt)
        remaining = remaining - amt.sum(axis=1, dtype=jnp.int32)
        # price update: a capacity conflict is only real when the round
        # EXHAUSTED the node (the winner drained all it could hold) —
        # only then do this round's losers pay to go elsewhere. Pricing
        # every contested node (the classic rule) actively spreads
        # bidders away from the fullest feasible nodes, which is
        # anti-packing under a BestFit objective; with exhaustion-gated
        # prices the losers re-converge on near-full nodes next round,
        # so the auction behaves as a synchronized global BestFit that
        # interleaves heterogeneous asks per node — the axis on which
        # it beats the per-eval greedy chain
        bids_per_node = jnp.zeros(n, jnp.int32).at[flat_idx].add(
            active.reshape(-1).astype(jnp.int32))
        filled = won & (cap > 0) & (amt.astype(cap.dtype) >= cap)
        node_filled = jnp.zeros(n, jnp.bool_).at[flat_idx].max(
            filled.reshape(-1))
        price = price + price_eps * (
            node_filled & (bids_per_node > 1)).astype(f)
        return (used, remaining, take, price, rnd + 1, jnp.any(amt > 0))

    def cond(state):
        _, remaining, _, _, rnd, progressed = state
        return (rnd < rounds) & progressed & jnp.any(remaining > 0)

    init = (used0, k.astype(jnp.int32), jnp.zeros((g, n), jnp.int32),
            jnp.zeros(n, f), jnp.int32(0), jnp.bool_(True))
    used, _, take, _, rnd, _ = jax.lax.while_loop(cond, body, init)
    return used, take, rnd


@partial(jax.jit, static_argnames=("g", "rounds"), donate_argnums=(0,))
def solve_batch(
    used0,       # (N, D) f32 usage carry — device-RESIDENT, donated back
    available,   # (N, D) f32 resident capacity
    feas,        # (G, N) bool stacked per-eval feasibility masks
    aff,         # (G, N) f32 stacked per-eval affinity boosts
    ask,         # (G, D) f32 per-eval resource asks
    k,           # (G,) int32 placements wanted per eval
    tg_count,    # (G,) f32 (signature parity with solve_bulk_multi)
    seeds,       # (G,) uint32 per-eval tie-break seeds
    cidx,        # (C,) int32 usage-correction node rows (0 = no-op slot)
    cdelta,      # (C, D) f32 usage-correction deltas (see solver.py)
    evict=None,  # (N, D) f32 victim budgets (build_victim_tensors
                 #       .evictable) — None = victim-blind legacy graph
    net_prio=None,  # (N,) f32 preemptible-set netPriority aggregate
    *,
    g: int,
    rounds: int = MAX_ROUNDS,
):
    """Solve G evals' placements as ONE assignment problem -> ((N, D)
    new usage carry staying on device, (G, N) int16 per-eval counts,
    (6,) f32 info row — the counts + info pair is the only readback).

    Signature-compatible with kernels.solve_bulk_multi so the
    BulkSolverService can route a batch through either tier. Runs BOTH
    the auction and the exact greedy chain from the same start state
    inside this one launch and returns whichever assignment wins on
    (total placed, packing score) — per-eval rows keep their own counts
    either way, so per-job plan boundaries survive downstream.

    With `evict`/`net_prio` the auction arm also bids over each node's
    preemption victim budget (extra reclaimable capacity, penalty-scored
    and exhaustion-gated — see _auction); the greedy chain stays
    victim-blind by design, so the portfolio's safety arm never commits
    an assignment that needs evictions to be legal.

    info row: [auction_score, greedy_score, placed_auction,
    placed_greedy, rounds_run, auction_won].
    """
    n, d = available.shape
    f = available.dtype
    used0 = jnp.maximum(used0.at[cidx].add(cdelta), 0.0)
    pscore = (None if net_prio is None else
              1.0 / (1.0 + jnp.exp(0.0048 * (net_prio - 2048.0))))

    # greedy arm: the exact tpu-binpack chain, corrections already
    # folded above so the impl's fold sees no-op slots
    zero_cidx = jnp.zeros(1, jnp.int32)
    zero_cdelta = jnp.zeros((1, d), f)
    with jax.named_scope("greedy_arm"):
        used_greedy, counts_greedy = _solve_bulk_multi_impl(
            used0, available, feas, aff, ask, k, tg_count, seeds,
            zero_cidx, zero_cdelta, g=g)

    # auction arm: one run per PORTFOLIO entry from the same start state
    # with fresh tie-break jitter each time (scaled per entry); keep the
    # lexicographically best (placed, score) assignment, earliest
    # restart on exact ties. Unrolled python loop (not vmap) so the
    # sharded mirror in sharding.py can use the identical selection
    # chain bit-for-bit, and so each restart's (jitter_scale,
    # price_temp) bakes in as trace-time constants.
    used_auction = take = rnd = None
    score_best = placed_best = None
    for t, (jscale, ptemp) in enumerate(PORTFOLIO):
        jits = jax.vmap(
            lambda s, _t=t, _js=jscale: jax.random.uniform(
                jax.random.fold_in(jax.random.PRNGKey(s), _t), (n,),
                jnp.float32, 0.0, TIE_JITTER * _js)
        )(seeds)                                                  # (G, N)
        # one scope a restart: the arms split the launch in the trace
        with jax.named_scope(f"auction_arm_{t}"):
            used_t, take_t, rnd_t = _auction(
                used0, available, feas, aff, ask, k, jits, g, rounds,
                price_eps=PRICE_EPS * ptemp, evict=evict, pscore=pscore)
        # dtype pin: placement counts reduce as int32 (associative adds
        # — legal before a comparison; x64 would promote to int64)
        placed_t = take_t.sum(dtype=jnp.int32)
        score_t = _packing_score_xp(jnp, take_t, available, used_t)
        if t == 0:
            used_auction, take, rnd = used_t, take_t, rnd_t
            score_best, placed_best = score_t, placed_t
        else:
            better = (placed_t > placed_best) | (
                (placed_t == placed_best) & (score_t > score_best))
            used_auction = jnp.where(better, used_t, used_auction)
            take = jnp.where(better, take_t, take)
            rnd = jnp.where(better, rnd_t, rnd)
            score_best = jnp.where(better, score_t, score_best)
            placed_best = jnp.where(better, placed_t, placed_best)

    placed_a = take.sum(dtype=jnp.int32)
    placed_g = counts_greedy.astype(jnp.int32).sum()
    score_a = _packing_score_xp(jnp, take, available, used_auction)
    score_g = _packing_score_xp(jnp, counts_greedy.astype(jnp.int32),
                                available, used_greedy)
    # portfolio pick: more placements first, then packing score — the
    # selected assignment is never worse than greedy on either axis
    pick_a = (placed_a > placed_g) | (
        (placed_a == placed_g) & (score_a > score_g))
    used = jnp.where(pick_a, used_auction, used_greedy)
    counts = jnp.where(pick_a, take.astype(jnp.int16), counts_greedy)
    info = jnp.stack([
        score_a.astype(jnp.float32), score_g.astype(jnp.float32),
        placed_a.astype(jnp.float32), placed_g.astype(jnp.float32),
        rnd.astype(jnp.float32), pick_a.astype(jnp.float32)])
    return used, counts, info
