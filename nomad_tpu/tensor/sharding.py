"""Multi-chip sharding for the placement solve.

The long axis of this workload is nodes (SURVEY.md §5: the (jobs x nodes)
matrix is our "long context"). The solve is embarrassingly parallel over
nodes except for one global reduction per placement step (the argmax over
node scores) and one scatter (the usage update on the winner) — exactly
the shape of ring-reduce workloads, so it rides ICI:

    mesh = Mesh(devices, ("nodes",))
    available, used, feasible, ...  sharded P("nodes")   [row-sharded]
    spread tables, ask, flags       replicated P()
    per-step: local scores -> global argmax (XLA all-reduce over ICI)
              -> one-hot usage update (local on the owning shard)

With jit + NamedSharding constraints XLA inserts the collectives; there
is no hand-written NCCL/MPI analog to port (the reference's comm backend
is msgpack-RPC/Serf/Raft, SURVEY.md §2.5 — control-plane replication
stays host-side, this module only distributes the math).

Engaged by the solver service by itself whenever the process holds more
than one device (tensor/solver.BulkSolverService._resolve_mesh);
chip_smoke.py drives it on a four-chip host.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# replication checking off: outputs declared P() (info row, gather
# counts) are replicated by construction — every shard runs the same
# math on the all-gathered pools — not by anything the checker can prove
_shard_map = partial(shard_map, check_vma=False)


def node_mesh(devices: Sequence = None, axis: str = "nodes") -> Mesh:
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis,))


def pad_node_axis(args: tuple, multiple: int) -> tuple:
    """Pad the node axis up to a multiple of the mesh size with infeasible
    dummy rows (available=0, feasible=False, spread_val_ok=False). The
    solve's argmax can never pick them, so choices stay valid indices into
    the real rows and scores are untouched — real clusters are rarely
    divisible by the device count."""
    n = args[0].shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return args
    args = list(args)

    def _pad(x, axis, value):
        x = np.asarray(x)
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return np.pad(x, widths, constant_values=value)

    args[0] = _pad(args[0], 0, 0)          # available
    args[1] = _pad(args[1], 0, 0)          # used0
    args[2] = _pad(args[2], 0, 0)          # placed_tg0
    args[3] = _pad(args[3], 0, 0)          # placed_job0
    args[5] = _pad(args[5], 0, False)      # feasible
    args[6] = _pad(args[6], 0, 0.0)        # affinity_boost
    args[7] = _pad(args[7], 0, 0.0)        # dev_affinity
    args[10] = _pad(args[10], 1, 0)        # spread_val_id
    args[11] = _pad(args[11], 1, False)    # spread_val_ok
    args[16] = _pad(args[16], 1, 0)        # dp_val_id
    args[17] = _pad(args[17], 1, False)    # dp_val_ok
    if len(args) > 25 and args[25] is not None:
        # tie_perm: dummy rows get the lowest priority, appended at the end
        args[25] = np.concatenate([
            np.asarray(args[25], np.int32), np.arange(n, n + pad, dtype=np.int32)])
    return tuple(args)


def shard_solve_args(mesh: Mesh, args: tuple, axis: str = "nodes"):
    """Device_put the solve_task_group argument tuple with node-axis rows
    sharded and everything else replicated. Pads the node axis to the
    mesh size first (see pad_node_axis).

    Argument order mirrors kernels.solve_task_group:
      0 available (N,D)   sharded   10 spread_val_id (S,N)  sharded ax1
      1 used0 (N,D)       sharded   11 spread_val_ok (S,N)  sharded ax1
      2 placed_tg0 (N,)   sharded   12 spread_counts0 (S,V) repl
      3 placed_job0 (N,)  sharded   13 spread_desired (S,V) repl
      4 ask (D,)          repl      14 spread_has_targets   repl
      5 feasible (N,)     sharded   15 spread_weight (S,)   repl
      6 affinity (N,)     sharded   16 dp_val_id (P,N)      sharded ax1
      7 dev_affinity (N,) sharded   17 dp_val_ok (P,N)      sharded ax1
      8 penalty_idx (K,)  repl      18 dp_counts0 (P,Vd)    repl
      9 active (K,)       repl      19 dp_limit (P,)        repl
                                    20..24 scalars          repl
                                    25 tie_perm (N,)        repl
    """
    args = pad_node_axis(args, int(np.prod(mesh.devices.shape)))
    specs = [
        P(axis, None), P(axis, None), P(axis), P(axis),
        P(), P(axis), P(axis), P(axis), P(), P(),
        P(None, axis), P(None, axis), P(), P(), P(), P(),
        P(None, axis), P(None, axis), P(), P(),
    ]
    specs += [P()] * (len(args) - len(specs))
    out = []
    for a, spec in zip(args, specs):
        out.append(a if a is None
                   else jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


def solve_task_group_sharded(mesh: Mesh, args: tuple, axis: str = "nodes"):
    """Run the placement solve with the node axis sharded over `mesh`.

    The same jitted kernel as the single-chip path: XLA propagates the
    input shardings through the scan and inserts ICI collectives for the
    global argmax each step. One collective PER PLACEMENT makes this
    latency-bound (round 4 measured it 7.3x slower than single-device at
    5K nodes) — it remains the general-semantics path (spread/
    distinct_hosts need per-placement rescoring), while the flagship
    bulk engine uses solve_bulk_multi_sharded below: one all-gather per
    EVAL, which is where the C2M scale lives.
    """
    from .kernels import solve_task_group

    sharded = shard_solve_args(mesh, args, axis)
    return solve_task_group(*sharded)


# --------------------------------------------------------------------------
# Sharded bulk engine (the C2M path on a mesh)
# --------------------------------------------------------------------------

def shard_bulk_state(mesh: Mesh, used0: np.ndarray, available: np.ndarray,
                     axis: str = "nodes"):
    """Device_put the bulk carry + capacity row-sharded over the mesh.
    The node axis must divide by the mesh size (ClusterStatic pads to a
    power of two, mesh sizes are powers of two)."""
    n_dev = int(np.prod(mesh.devices.shape))
    assert used0.shape[0] % n_dev == 0, (used0.shape, n_dev)
    sh = NamedSharding(mesh, P(axis, None))
    return (jax.device_put(np.asarray(used0, np.float32), sh),
            jax.device_put(np.asarray(available, np.float32), sh))


_STATE_SCATTER_CACHE: dict = {}


def make_state_scatter_sharded(mesh: Mesh, axis: str = "nodes",
                               donate: bool = True):
    """Row-sharded twin of the incremental state's delta scatter
    (tensor/incremental._scatter_fn): (used (N,D) sharded P(axis,None),
    idx (B,) replicated, delta (B,D) replicated) -> used with
    used[idx] += delta. Each shard masks off-shard rows to a zero delta
    and clips the index local — the same correction-fold idiom as
    _bulk_shard_body, so the result is bit-exact vs the single-device
    scatter (adds of integral f32 values commute exactly; a zero add is
    an exact no-op, usage rows are never -0.0). Jitted per (mesh,
    donate); donate=False is the solver's resync fold, which must keep
    the feed's twin alive behind the copy."""
    key = (mesh, axis, donate)
    fn = _STATE_SCATTER_CACHE.get(key)
    if fn is not None:
        return fn
    import jax.numpy as jnp

    def state_scatter_sharded(used, idx, delta):
        n_loc = used.shape[0]
        me = jax.lax.axis_index(axis)
        lo = me * n_loc
        local = idx - lo
        own = (local >= 0) & (local < n_loc)
        safe = jnp.clip(local, 0, n_loc - 1)
        return used.at[safe].add(jnp.where(own[:, None], delta, 0.0))

    body = _shard_map(state_scatter_sharded, mesh=mesh,
                in_specs=(P(axis, None), P(), P()),
                out_specs=P(axis, None))
    fn = (jax.jit(body, donate_argnums=(0,)) if donate
          else jax.jit(body))
    _STATE_SCATTER_CACHE[key] = fn
    return fn


def _bulk_shard_body(used0, avail, feas, aff, ask, k, seeds, cidx, cdelta,
                     *, g: int, axis: str, n_dev: int, top_r: int):
    """Per-shard body of the distributed greedy bulk fill (the math of
    kernels._solve_bulk_multi_impl over row-sharded nodes). Module-level
    so the joint batch solver's shard body can inline it as the greedy
    arm of its portfolio — must run inside a shard_map over `axis`."""
    import jax.numpy as jnp

    from .kernels import NEG, TIE_JITTER, fit_scores

    n_loc, d = used0.shape
    n = n_loc * n_dev
    r = min(top_r, n_loc)
    me = jax.lax.axis_index(axis)
    lo = me * n_loc
    # fold usage corrections: global rows -> local rows, off-shard
    # slots masked to zero delta
    local = cidx - lo
    own = (local >= 0) & (local < n_loc)
    safe = jnp.clip(local, 0, n_loc - 1)
    used0 = jnp.maximum(
        used0.at[safe].add(
            jnp.where(own[:, None], cdelta, 0.0)), 0.0)

    def one_eval(used, gi):
        ask_g = ask[gi]
        ask_pos = ask_g > 0
        new_used = used + ask_g[None, :]
        ok = feas[gi] & jnp.all(new_used <= avail, axis=1)
        fitness = fit_scores(avail, new_used, False)
        aff_g = aff[gi]
        aff_present = aff_g != 0.0
        score = ((fitness + jnp.where(aff_present, aff_g, 0.0))
                 / (1.0 + aff_present.astype(jnp.float32)))
        score = jnp.where(ok, score, NEG)
        free = avail - used
        per_dim = jnp.where(
            ask_pos[None, :],
            jnp.floor(free / jnp.where(ask_pos, ask_g, 1.0)[None, :]),
            jnp.inf)
        cap = jnp.clip(jnp.min(per_dim, axis=1), 0, None)
        cap = jnp.where(score > NEG, cap, 0.0)
        budget0 = k[gi]
        cap = jnp.minimum(cap, budget0.astype(cap.dtype)).astype(
            jnp.int32)
        # same jitter stream as the single-device kernel, sliced to
        # this shard's rows (global (N,) generated then sliced so
        # the values per node agree across layouts)
        jit_all = jax.random.uniform(
            jax.random.PRNGKey(seeds[gi]), (n,), jnp.float32, 0.0,
            TIE_JITTER)
        key0 = score + jax.lax.dynamic_slice(jit_all, (lo,), (n_loc,))

        def round_body(state):
            take_loc, cap_loc, key_loc, budget, rnd, _ = state
            masked = jnp.where(cap_loc > 0, key_loc, NEG)
            vals, loc_idx = jax.lax.top_k(masked, r)
            pool = jnp.stack([
                vals,
                cap_loc[loc_idx].astype(jnp.float32),
                (loc_idx + lo).astype(jnp.float32),
            ])                                            # (3, R)
            pools = jax.lax.all_gather(pool, axis)        # (ndev,3,R)
            keys_all = pools[:, 0, :].reshape(-1)
            caps_all = pools[:, 1, :].reshape(-1).astype(jnp.int32)
            gidx_all = pools[:, 2, :].reshape(-1).astype(jnp.int32)
            # consume-safety threshold: worst pool entry of the
            # best-covered shard — anything above it beats every
            # node no shard surfaced this round
            thresh = jnp.max(pools[:, 0, r - 1])
            # keys desc, global index asc on ties (matches the
            # single-device stable argsort exactly)
            order = jnp.lexsort((gidx_all, -keys_all))
            keys_s = keys_all[order]
            caps_s = caps_all[order]
            eligible = keys_s > thresh
            # progress guarantee: the global best always consumes
            eligible = eligible.at[0].set(keys_s[0] > NEG)
            caps_e = jnp.where(eligible, caps_s, 0)
            cum = jnp.cumsum(caps_e).astype(jnp.int32)
            take_s = jnp.clip(budget - (cum - caps_e), 0, caps_e)
            # int32 pin: integer adds are associative, and the result
            # feeds the round-progress comparisons below
            consumed = jnp.sum(take_s, dtype=jnp.int32).astype(
                budget.dtype)
            # scatter back: mark eligible candidates consumed (cap
            # 0) and add takes on our own rows
            take_c = jnp.zeros_like(caps_all).at[order].set(take_s)
            elig_c = jnp.zeros(caps_all.shape, bool).at[order].set(
                eligible)
            pos = gidx_all - lo
            mine = (pos >= 0) & (pos < n_loc)
            posc = jnp.clip(pos, 0, n_loc - 1)
            take_loc = take_loc.at[posc].add(
                jnp.where(mine, take_c, 0))
            cap_loc = cap_loc.at[posc].multiply(
                jnp.where(mine & elig_c, 0, 1))
            budget = budget - consumed
            go = (budget > 0) & (keys_s[0] > NEG) & (consumed > 0)
            return take_loc, cap_loc, key_loc, budget, rnd + 1, go

        def round_cond(state):
            return state[5]

        init = (jnp.zeros(n_loc, jnp.int32), cap, key0, budget0,
                jnp.int32(0), budget0 > 0)
        take_loc, _, _, _, rnd, _ = jax.lax.while_loop(
            round_cond, round_body, init)
        used = used + ask_g[None, :] * take_loc[:, None].astype(
            used.dtype)
        # rnd == all-gathers this eval consumed (one per round); the
        # while state is replicated math so every shard reports the same
        # value — the launch's collective cadence, surfaced so the bench
        # can prove the one-gather-per-eval contract held at scale
        return used, (take_loc.astype(jnp.int16), rnd)

    used, (counts, rounds) = jax.lax.scan(one_eval, used0, jnp.arange(g))
    return used, counts, rounds


def make_solve_bulk_multi_sharded(mesh: Mesh, axis: str = "nodes",
                                  top_r: int = 64):
    """Build the mesh-sharded twin of kernels.solve_bulk_multi.

    Layout: capacity/carry/masks row-sharded over `axis`; asks/budgets
    replicated. Per eval, the fill runs as a short round loop of
    DISTRIBUTED top-k selection:

      round: each shard takes its local top-R candidates by jittered
             score (local compute, no collective) -> ONE tiled
             all-gather of the (R,) keys/caps/ids per shard -> every
             device merges the <= R*n_dev candidates (a tiny sort) and
             consumes, in global key order, every candidate whose key
             beats the WORST pool entry of every shard (those provably
             outrank all unseen nodes) until the budget is filled ->
             each shard applies its own slice of the usage update.

    Fill-to-capacity means the number of consuming rounds is
    ~touched_nodes / (R * n_dev) — almost always 1 — so the collective
    cadence is O(G) tiny gathers per launch, vs O(K) global argmaxes
    for the per-placement scan (round 4's 7.3x sharded slowdown), and
    no step replicates O(N log N) sort work. Tie-breaks are the same
    additive score jitter as the single-device kernel; counts agree
    exactly with kernels.solve_bulk_multi.

    Returns solve(used0_sharded, avail_sharded, feas, aff, ask, k,
    seeds, cidx, cdelta, *, g) -> (new_used sharded, (G, N) int16
    counts sharded on the node axis, (G,) int32 replicated all-gather
    rounds per eval — the launch's collective cadence).
    """
    n_dev = int(np.prod(mesh.devices.shape))

    @partial(jax.jit, static_argnames=("g",), donate_argnums=(0,))
    def solve(used0, avail, feas, aff, ask, k, seeds, cidx, cdelta, *,
              g: int):
        fn = _shard_map(
            partial(_bulk_shard_body, g=g, axis=axis, n_dev=n_dev,
                    top_r=top_r),
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(None, axis),
                      P(None, axis), P(), P(), P(), P(), P()),
            out_specs=(P(axis, None), P(None, axis), P()))
        return fn(used0, avail, feas, aff, ask, k, seeds, cidx, cdelta)

    return solve


def make_solve_batch_sharded(mesh: Mesh, axis: str = "nodes",
                             top_r: int = 64):
    """Build the mesh-sharded twin of batch_solver.solve_batch (the
    "tpu-solve" joint auction over a whole eval batch).

    Layout matches make_solve_bulk_multi_sharded: carry/capacity
    row-sharded, per-eval masks column-sharded, asks/budgets replicated.
    Per AUCTION ROUND (not per eval, not per placement):

      each shard computes its local (G, n_loc) bid matrix and its local
      top-R candidates per eval (bid, capacity, global node id) -> ONE
      all-gather of the (3, G, R) pools -> every device merges them
      into each eval's EXACT global top-R (value desc, node id asc —
      the same order single-device top_k yields, so counts agree
      bit-exactly across layouts), resolves per-node winners and the
      winners' score-ordered capacity fills over the <= G*R candidates
      (replicated small-matrix work) -> each shard applies the usage
      updates for the rows it owns; the price vector stays replicated.

    So the collective cadence is one small all-gather per round, and
    rounds converge in a handful (~touched_nodes / TOP_R, see
    batch_solver.MAX_ROUNDS) — independent of both K and G, vs O(G)
    gathers for the sharded greedy chain. The greedy arm of the
    portfolio reuses _bulk_shard_body inside the SAME shard_map, and
    the arm-selection scores reduce with one psum each.

    Returns solve(used0_sharded, avail_sharded, feas, aff, ask, k,
    seeds, cidx, cdelta, *, g) -> (new_used sharded, (G, N) int16
    counts sharded on the node axis, (6,) f32 replicated info row with
    the same layout as batch_solver.solve_batch, plus a replicated
    int32 scalar counting the launch's all-gathers across every
    portfolio arm and the greedy chain).
    """
    import jax.numpy as jnp

    from .batch_solver import (MAX_ROUNDS, PORTFOLIO, PRICE_EPS, TOP_R,
                               _pairwise_sum_xp)
    from .kernels import NEG, TIE_JITTER

    n_dev = int(np.prod(mesh.devices.shape))

    def _joint_body(used0, avail, feas, aff, ask, k, seeds, cidx, cdelta,
                    evict=None, net_prio=None, *, g: int):
        from .kernels import _fit_scores_xp as fit_xp

        n_loc, d = used0.shape
        n = n_loc * n_dev
        f = used0.dtype
        me = jax.lax.axis_index(axis)
        lo = me * n_loc
        # victim budgets (row-sharded like avail); pscore is local too
        avail_cap = avail if evict is None else avail + evict
        pscore_loc = (None if net_prio is None else
                      1.0 / (1.0 + jnp.exp(0.0048 * (net_prio - 2048.0))))
        # int32 throughout the carry (x64 mode: arange defaults int64,
        # sum() promotes int32 -> int64 — both break the loop carry)
        g_idx = jnp.arange(g, dtype=jnp.int32)
        # fold corrections (global rows -> local), as the bulk body does
        local = cidx - lo
        own = (local >= 0) & (local < n_loc)
        safe = jnp.clip(local, 0, n_loc - 1)
        used0 = jnp.maximum(
            used0.at[safe].add(jnp.where(own[:, None], cdelta, 0.0)), 0.0)

        # greedy arm: the distributed bulk fill from the same start
        # state (corrections already folded -> no-op slots)
        used_g, counts_g, rounds_g = _bulk_shard_body(
            used0, avail, feas, aff, ask, k, seeds,
            jnp.zeros(1, jnp.int32), jnp.zeros((1, d), f),
            g=g, axis=axis, n_dev=n_dev, top_r=top_r)
        # collective cadence of the whole launch: the greedy arm's
        # per-eval gathers plus one gather per auction round per
        # portfolio restart (accumulated below) — replicated math
        gathers = jnp.sum(rounds_g)

        ask_pos = ask > 0
        aff_present = aff != 0.0
        divisor = 1.0 + aff_present.astype(f)

        r_loc = min(TOP_R, n_loc)
        r_glob = min(TOP_R, n)

        def body(state, jits, price_eps):
            used, remaining, take, price, rnd, _ = state
            price_loc = jax.lax.dynamic_slice(price, (lo,), (n_loc,))
            new_used = used[None, :, :] + ask[:, None, :]     # (G,nl,D)
            ok = feas & jnp.all(new_used <= avail_cap[None, :, :], axis=2)
            ok &= (remaining > 0)[:, None]
            if evict is None:
                fitness = fit_xp(jnp, avail[None, :, :], new_used, False)
                score = (fitness
                         + jnp.where(aff_present, aff, 0.0)) / divisor
            else:
                # over-capacity bids spend victim budget (mirrors the
                # single-device eviction branch exactly)
                fitness = fit_xp(
                    jnp, avail[None, :, :],
                    jnp.minimum(new_used, avail[None, :, :]), False)
                over = jnp.any(new_used > avail[None, :, :], axis=2)
                score = (fitness + jnp.where(aff_present, aff, 0.0)
                         + jnp.where(over, pscore_loc[None, :], 0.0)) / (
                             divisor + over.astype(f))
            bid = jnp.where(ok, score + jits - price_loc[None, :], NEG)
            lvals, lidx = jax.lax.top_k(bid, r_loc)           # (G, RL)
            free = avail_cap[lidx] - used[lidx]               # (G,RL,D)
            per_dim = jnp.where(
                ask_pos[:, None, :],
                jnp.floor(free
                          / jnp.where(ask_pos, ask, 1.0)[:, None, :]),
                jnp.inf)
            lcap = jnp.clip(jnp.min(per_dim, axis=2), 0, None)
            pool = jnp.stack([
                lvals, lcap.astype(jnp.float32),
                (lidx + lo).astype(jnp.float32)])             # (3,G,RL)
            pools = jax.lax.all_gather(pool, axis)          # (ndev,3,G,RL)
            vals_m = pools[:, 0].transpose(1, 0, 2).reshape(g, -1)
            caps_m = pools[:, 1].transpose(1, 0, 2).reshape(g, -1)
            gids_m = pools[:, 2].transpose(1, 0, 2).reshape(g, -1)
            # merge to each eval's EXACT global top-R, ordered (value
            # desc, node id asc) — what single-device top_k over the
            # full row yields, so every layout sees the same candidates
            neg_s, gid_s, cap_s = jax.lax.sort(
                (-vals_m, gids_m, caps_m), dimension=1, num_keys=2)
            vals = -neg_s[:, :r_glob]                         # (G, R)
            gids = gid_s[:, :r_glob].astype(jnp.int32)
            caps = cap_s[:, :r_glob]
            active = vals > NEG / 2
            flat_gid = gids.reshape(-1)
            flat_val = jnp.where(active, vals, NEG).reshape(-1)
            flat_g = jnp.broadcast_to(
                g_idx[:, None], gids.shape).reshape(-1)
            # winner per node among all surfaced candidates — the
            # (N,)-sized boards stay replicated (same math every shard)
            node_best = jnp.full(n, NEG, f).at[flat_gid].max(flat_val)
            is_best = ((flat_val > NEG / 2)
                       & (flat_val >= node_best[flat_gid]))
            node_winner = jnp.full(n, g, jnp.int32).at[flat_gid].min(
                jnp.where(is_best, flat_g, g))
            won = active & (vals >= node_best[gids]) & (
                node_winner[gids] == g_idx[:, None])          # (G, R)
            cap_w = jnp.where(won, caps, 0.0)
            # spend remaining demand across won nodes in score order
            prefix = jnp.cumsum(cap_w, axis=1) - cap_w
            amt = jnp.clip(remaining.astype(f)[:, None] - prefix,
                           0.0, cap_w).astype(jnp.int32)      # (G, R)
            # each shard applies the rows it owns
            pos = gids - lo
            mine = (pos >= 0) & (pos < n_loc)
            posc = jnp.clip(pos, 0, n_loc - 1)
            amt_mine = jnp.where(mine, amt, 0)
            used = used.at[posc.reshape(-1)].add(
                (ask[:, None, :] * amt_mine[..., None].astype(f)
                 ).reshape(-1, d))
            take = take.at[g_idx[:, None], posc].add(amt_mine)
            remaining = remaining - amt.sum(
                axis=1, dtype=jnp.int32)             # replicated math
            # exhaustion-gated price bump, replicated math (see the
            # single-device body for why contested alone is not enough)
            bids_per_node = jnp.zeros(n, jnp.int32).at[flat_gid].add(
                active.reshape(-1).astype(jnp.int32))
            filled = won & (cap_w > 0) & (amt.astype(f) >= cap_w)
            node_filled = jnp.zeros(n, jnp.bool_).at[flat_gid].max(
                filled.reshape(-1))
            price = price + price_eps * (
                node_filled & (bids_per_node > 1)).astype(f)
            return (used, remaining, take, price, rnd + 1,
                    jnp.any(amt > 0))

        def cond(state):
            _, remaining, _, _, rnd, progressed = state
            return ((rnd < MAX_ROUNDS) & progressed
                    & jnp.any(remaining > 0))

        # auction arm: one run per PORTFOLIO (jitter_scale, price_temp)
        # entry with fresh tie-break jitter each time (same fold_in
        # stream as the single-device kernel, global (N,) generated then
        # sliced so values per node agree across layouts); selection
        # chain mirrors batch_solver.solve_batch exactly — earliest
        # restart wins exact ties — so counts stay bit-identical to the
        # single-device path
        def det_score(take2d, used_loc):
            # bit-identical to the single-device _packing_score_xp:
            # gather the per-node contributions and reduce over the
            # GLOBAL node order with the same fixed pairwise tree. A
            # psum of per-shard partial sums reassociates the float
            # adds per mesh size, and a one-ulp score wobble is enough
            # to flip a near-tied portfolio selection — breaking
            # cross-mesh count parity
            contrib = (take2d.sum(axis=0).astype(f)
                       * fit_xp(jnp, avail, used_loc, False))  # (n_loc,)
            return _pairwise_sum_xp(
                jnp, jax.lax.all_gather(contrib, axis).reshape(-1))

        used_a = take = rnd = None
        score_a = placed_a = None
        for t, (jscale, ptemp) in enumerate(PORTFOLIO):
            jits = jax.vmap(lambda s, _t=t, _js=jscale: jax.lax.dynamic_slice(
                jax.random.uniform(
                    jax.random.fold_in(jax.random.PRNGKey(s), _t), (n,),
                    jnp.float32, 0.0, TIE_JITTER * _js),
                (lo,), (n_loc,)))(seeds)
            init = (used0, k.astype(jnp.int32),
                    jnp.zeros((g, n_loc), jnp.int32), jnp.zeros(n, f),
                    jnp.int32(0), jnp.bool_(True))
            used_t, _, take_t, _, rnd_t, _ = jax.lax.while_loop(
                cond, lambda st, j=jits, pe=PRICE_EPS * ptemp:
                body(st, j, pe), init)
            # +1 for the det_score gather (placed stays a psum: integer
            # adds are associative, so it cannot wobble)
            gathers = gathers + rnd_t + 1
            placed_t = jax.lax.psum(take_t.sum(dtype=jnp.int32), axis)
            score_t = det_score(take_t, used_t)
            if t == 0:
                used_a, take, rnd = used_t, take_t, rnd_t
                score_a, placed_a = score_t, placed_t
            else:
                better = (placed_t > placed_a) | (
                    (placed_t == placed_a) & (score_t > score_a))
                used_a = jnp.where(better, used_t, used_a)
                take = jnp.where(better, take_t, take)
                rnd = jnp.where(better, rnd_t, rnd)
                score_a = jnp.where(better, score_t, score_a)
                placed_a = jnp.where(better, placed_t, placed_a)

        # portfolio selection vs greedy on globally-reduced scores
        placed_g = jax.lax.psum(counts_g.astype(jnp.int32).sum(), axis)
        score_g = det_score(counts_g.astype(jnp.int32), used_g)
        gathers = gathers + 1
        pick_a = (placed_a > placed_g) | (
            (placed_a == placed_g) & (score_a > score_g))
        used = jnp.where(pick_a, used_a, used_g)
        counts = jnp.where(pick_a, take.astype(jnp.int16), counts_g)
        info = jnp.stack([
            score_a.astype(jnp.float32), score_g.astype(jnp.float32),
            placed_a.astype(jnp.float32), placed_g.astype(jnp.float32),
            rnd.astype(jnp.float32), pick_a.astype(jnp.float32)])
        return used, counts, info, gathers

    @partial(jax.jit, static_argnames=("g",), donate_argnums=(0,))
    def solve(used0, avail, feas, aff, ask, k, seeds, cidx, cdelta,
              evict=None, net_prio=None, *, g: int):
        base_specs = (P(axis, None), P(axis, None), P(None, axis),
                      P(None, axis), P(), P(), P(), P(), P())
        out = (P(axis, None), P(None, axis), P(), P())
        if evict is None:
            fn = _shard_map(
                partial(_joint_body, g=g), mesh=mesh,
                in_specs=base_specs, out_specs=out)
            return fn(used0, avail, feas, aff, ask, k, seeds, cidx,
                      cdelta)
        # victim budgets ride the node axis like avail; net_prio is a
        # plain (N,) node row
        fn = _shard_map(
            partial(_joint_body, g=g), mesh=mesh,
            in_specs=base_specs + (P(axis, None), P(axis)),
            out_specs=out)
        return fn(used0, avail, feas, aff, ask, k, seeds, cidx, cdelta,
                  evict, net_prio)

    return solve
