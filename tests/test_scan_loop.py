"""The per-placement loop of kernels.solve_task_group against the plain
reference: the `lax.scan` over all K padded rows it replaced, kept here
and nowhere else. The loop runs as many steps as placements are asked
and carries the node state row by row; what it computes must not move:
choice, found and score equal bit for bit on every active row, and
nothing found past the bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.tensor.kernels import (NEG, _pairwise_sum_xp, scan_steps,
                                      solve_task_group)

F32 = np.float32


# ---------------------------------------------------------------------------
# the reference: the scan as it was, formula and all
# ---------------------------------------------------------------------------


def _ref_fit_scores(available, used, spread_alg):
    safe = jnp.where(available > 0, available, 1.0)
    ratio = jnp.where(available > 0, used / safe,
                      jnp.where(used > 0, jnp.inf, 0.0))
    free = 1.0 - ratio
    total = 10.0 ** free[..., 0] + 10.0 ** free[..., 1]
    binpack = jnp.clip(20.0 - total, 0.0, 18.0)
    spread = jnp.clip(total - 2.0, 0.0, 18.0)
    return jnp.where(spread_alg, spread, binpack) / 18.0


def _ref_spread_boost(dtype, spread_val_id, spread_val_ok, spread_counts,
                      spread_desired, spread_has_targets, spread_weight,
                      lowest_boost):
    counts_at = jnp.take_along_axis(spread_counts, spread_val_id, axis=1)
    used_cnt = counts_at.astype(dtype) + 1.0
    desired = jnp.take_along_axis(spread_desired, spread_val_id, axis=1)
    explicit = jnp.where(
        jnp.isnan(desired), -1.0,
        jnp.where(desired == 0.0, lowest_boost,
                  (desired - used_cnt) / jnp.where(desired == 0.0, 1.0, desired)
                  * spread_weight[:, None]))
    explicit = jnp.where(spread_val_ok, explicit, -1.0)
    present_v = spread_counts > 0
    any_present = jnp.any(present_v, axis=1)
    minc = jnp.min(jnp.where(present_v, spread_counts,
                             jnp.iinfo(jnp.int32).max), axis=1).astype(dtype)
    maxc = jnp.max(jnp.where(present_v, spread_counts, 0),
                   axis=1).astype(dtype)
    cur = counts_at.astype(dtype)
    minc_b, maxc_b = minc[:, None], maxc[:, None]
    even = jnp.where(
        cur != minc_b,
        jnp.where(minc_b == 0.0, -1.0,
                  (minc_b - cur) / jnp.where(minc_b == 0.0, 1.0, minc_b)),
        jnp.where(minc_b == maxc_b, -1.0,
                  jnp.where(minc_b == 0.0, 1.0,
                            (maxc_b - minc_b)
                            / jnp.where(minc_b == 0.0, 1.0, minc_b))))
    even = jnp.where(any_present[:, None], even, 0.0)
    even = jnp.where(spread_val_ok, even, -1.0)
    boost = jnp.where(spread_has_targets[:, None], explicit, even)
    return _pairwise_sum_xp(jnp, boost), boost


def _ref_score_nodes(*, available, used, ask, feasible, placed_tg, placed_job,
                     affinity_boost, dev_affinity, penalty_idx, spread_val_id,
                     spread_val_ok, spread_counts, spread_desired,
                     spread_has_targets, spread_weight, dp_val_id, dp_val_ok,
                     dp_counts, dp_limit, lowest_boost, tg_count, dh_job,
                     dh_tg, spread_alg):
    n = available.shape[0]
    new_used = used + ask[None, :]
    ok = feasible & jnp.all(new_used <= available, axis=1)
    ok &= jnp.where(dh_job, placed_job == 0, True)
    ok &= jnp.where(dh_tg, placed_tg == 0, True)
    if dp_val_id.shape[0]:
        dp_at = jnp.take_along_axis(dp_counts, dp_val_id, axis=1)
        ok &= jnp.all(dp_val_ok & (dp_at < dp_limit[:, None]), axis=0)
    fitness = _ref_fit_scores(available, new_used, spread_alg)
    anti_present = placed_tg > 0
    anti = (-(placed_tg.astype(fitness.dtype) + 1.0)
            / jnp.maximum(tg_count, 1.0))
    resched_present = jnp.arange(n) == penalty_idx
    aff_present = affinity_boost != 0.0
    dev_present = dev_affinity != 0.0
    spread_total, boost = _ref_spread_boost(
        fitness.dtype, spread_val_id, spread_val_ok, spread_counts,
        spread_desired, spread_has_targets, spread_weight, lowest_boost)
    spread_present = spread_total != 0.0
    divisor = (1.0 + anti_present.astype(fitness.dtype)
               + resched_present.astype(fitness.dtype)
               + aff_present.astype(fitness.dtype)
               + dev_present.astype(fitness.dtype)
               + spread_present.astype(fitness.dtype))
    total = (fitness + jnp.where(anti_present, anti, 0.0)
             + jnp.where(resched_present, -1.0, 0.0)
             + jnp.where(aff_present, affinity_boost, 0.0)
             + jnp.where(dev_present, dev_affinity, 0.0)
             + jnp.where(spread_present, spread_total, 0.0))
    return jnp.where(ok, total / divisor, NEG), boost


@jax.jit
def _ref_solve_task_group(available, used0, placed_tg0, placed_job0, ask,
                          feasible, affinity_boost, dev_affinity, penalty_idx,
                          active, spread_val_id, spread_val_ok, spread_counts0,
                          spread_desired, spread_has_targets, spread_weight,
                          dp_val_id, dp_val_ok, dp_counts0, dp_limit,
                          lowest_boost0, tg_count, dh_job, dh_tg, spread_alg,
                          tie_perm):
    s, p, n = spread_val_id.shape[0], dp_val_id.shape[0], available.shape[0]
    (available, used0, placed_tg0, placed_job0, feasible, affinity_boost,
     dev_affinity) = (a[tie_perm] for a in (
         available, used0, placed_tg0, placed_job0, feasible, affinity_boost,
         dev_affinity))
    spread_val_id, spread_val_ok = (spread_val_id[:, tie_perm],
                                    spread_val_ok[:, tie_perm])
    if p:
        dp_val_id, dp_val_ok = dp_val_id[:, tie_perm], dp_val_ok[:, tie_perm]
    inv = jnp.zeros(n, jnp.int32).at[tie_perm].set(
        jnp.arange(n, dtype=jnp.int32))
    penalty_idx = jnp.where(penalty_idx >= 0, inv[penalty_idx], -1)

    def step(carry, xs):
        used, ptg, pjob, scnt, dpcnt, lowest = carry
        pen_idx, is_active = xs
        score, boost = _ref_score_nodes(
            available=available, used=used, ask=ask, feasible=feasible,
            placed_tg=ptg, placed_job=pjob, affinity_boost=affinity_boost,
            dev_affinity=dev_affinity, penalty_idx=pen_idx,
            spread_val_id=spread_val_id, spread_val_ok=spread_val_ok,
            spread_counts=scnt, spread_desired=spread_desired,
            spread_has_targets=spread_has_targets,
            spread_weight=spread_weight, dp_val_id=dp_val_id,
            dp_val_ok=dp_val_ok, dp_counts=dpcnt, dp_limit=dp_limit,
            lowest_boost=lowest, tg_count=tg_count, dh_job=dh_job,
            dh_tg=dh_tg, spread_alg=spread_alg)
        choice = jnp.argmax(score)
        found = is_active & (score[choice] > NEG)
        onehot = (jnp.arange(n) == choice) & found
        used = used + ask[None, :] * onehot[:, None]
        ptg = ptg + onehot.astype(ptg.dtype)
        pjob = pjob + onehot.astype(pjob.dtype)
        sel_ok = spread_val_ok[:, choice] & found
        sel_val = spread_val_id[:, choice]
        scnt = scnt.at[jnp.arange(s), sel_val].add(sel_ok.astype(scnt.dtype))
        if p:
            dsel_ok = dp_val_ok[:, choice] & found
            dsel_val = dp_val_id[:, choice]
            dpcnt = dpcnt.at[jnp.arange(p), dsel_val].add(
                dsel_ok.astype(dpcnt.dtype))
        chosen_boost = jnp.where(spread_has_targets & sel_ok,
                                 boost[:, choice], jnp.inf)
        lowest = jnp.minimum(lowest, jnp.min(chosen_boost, initial=jnp.inf))
        return ((used, ptg, pjob, scnt, dpcnt, lowest),
                (choice, found, score[choice]))

    init = (used0, placed_tg0, placed_job0, spread_counts0, dp_counts0,
            lowest_boost0)
    _, (choices, founds, scores) = jax.lax.scan(
        init=init, f=step, xs=(penalty_idx, active))
    return tie_perm[choices], founds, scores


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


def _args(seed, *, n=96, k_pad=32, k=20, s=1, targets=False, p=0, d=4,
          dh_job=False, dh_tg=False, spread_alg=False, penalties=False,
          v=4, active=None, fit=2.0, limit=6):
    """One solve's arguments in solve_task_group's order, f32 as the
    served path ships them. `fit` = placements the roomiest node takes."""
    rng = np.random.RandomState(seed)
    available = np.stack(
        [rng.choice([2000, 4000, 8000], n), rng.choice([4096, 8192], n),
         np.full(n, 100 * 1024), np.full(n, 12001)]
        + [rng.choice([0, 2, 4], n) for _ in range(d - 4)], axis=1).astype(F32)
    used0 = np.zeros((n, d), F32)
    used0[:, 0] = rng.randint(0, 1000, n)
    used0[:, 1] = rng.randint(0, 2048, n)
    ask = np.array([8000.0 / fit - 1000.0, 256.0, 0.0, 2.0]
                   + [1.0] * (d - 4), F32)
    feasible = rng.rand(n) > 0.1
    affinity = np.where(rng.rand(n) > 0.7, rng.choice([-0.5, 0.25, 1.0], n),
                        0.0).astype(F32)
    dev_affinity = (np.where(rng.rand(n) > 0.8, 0.5, 0.0).astype(F32)
                    if d > 4 else np.zeros(n, F32))
    penalty_idx = np.full(k_pad, -1, np.int32)
    if penalties:
        penalty_idx[::3] = rng.randint(0, n, len(penalty_idx[::3]))
    if active is None:
        active = np.zeros(k_pad, bool)
        active[:k] = True
    desired = np.full((s, v), np.nan, F32)
    has_targets = np.zeros(s, bool)
    if targets and s:
        # row 0 explicit targets (one value with none, one with 0), any
        # further row even spread
        desired[0] = [k // 2, k // 4, 0.0, np.nan][:v]
        has_targets[0] = True
    counts0 = rng.randint(0, 3, (s, v)).astype(np.int32)
    return (
        available, used0, rng.randint(0, 2, n).astype(np.int32),
        rng.randint(0, 2, n).astype(np.int32), ask, feasible, affinity,
        dev_affinity, penalty_idx, np.asarray(active, bool),
        rng.randint(0, v, (s, n)).astype(np.int32), rng.rand(s, n) > 0.05,
        counts0, desired, has_targets,
        np.full(s, 1.0 / max(s, 1), F32),
        rng.randint(0, v, (p, n)).astype(np.int32), rng.rand(p, n) > 0.05,
        rng.randint(0, 2, (p, v)).astype(np.int32), np.full(p, limit, F32),
        F32(-1.0), F32(k), np.bool_(dh_job), np.bool_(dh_tg),
        np.bool_(spread_alg),
        rng.permutation(n).astype(np.int32))


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32 if x.dtype == np.float32 else np.int64)


def _assert_same_placements(args):
    """-> (choices, founds, scores) of the loop, checked against the
    reference on every active row and empty past the bound."""
    active = np.asarray(args[9])
    bound = scan_steps(active)
    got = [np.asarray(a) for a in solve_task_group(*args)]
    want = [np.asarray(a) for a in _ref_solve_task_group(*args)]
    assert got[2].dtype == want[2].dtype == np.float32
    rows = np.flatnonzero(active)
    assert np.array_equal(got[1][rows], want[1][rows])
    assert np.array_equal(got[0][rows], want[0][rows])
    assert np.array_equal(_bits(got[2][rows]), _bits(want[2][rows]))
    # an inactive row before the bound runs its step and finds nothing
    assert not got[1][~active].any()
    assert not got[1][bound:].any() and not got[0][bound:].any()
    assert (got[2][bound:] == F32(NEG)).all()
    return got


# ---------------------------------------------------------------------------
# the loop against the scan
# ---------------------------------------------------------------------------

SPREADS = {"s0": dict(s=0), "s1_even": dict(s=1), "s1_targets": dict(s=1, targets=True),
           "s2_even": dict(s=2), "s2_mixed": dict(s=2, targets=True)}


@pytest.mark.parametrize("spread", sorted(SPREADS))
@pytest.mark.parametrize("p", [0, 1])
@pytest.mark.parametrize("d", [4, 6])
def test_loop_equals_scan_over_spread_property_and_dims(spread, p, d):
    got = _assert_same_placements(_args(7, p=p, d=d, **SPREADS[spread]))
    assert got[1][:20].any()


@pytest.mark.parametrize("flags", [
    dict(dh_job=True), dict(dh_tg=True), dict(dh_job=True, dh_tg=True),
    dict(spread_alg=True), dict(penalties=True),
    dict(penalties=True, spread_alg=True, dh_tg=True)],
    ids=lambda f: "+".join(sorted(f)))
@pytest.mark.parametrize("spread", ["s0", "s2_mixed"])
def test_loop_equals_scan_over_flags(flags, spread):
    got = _assert_same_placements(
        _args(11, p=1, **SPREADS[spread], **flags))
    assert got[1].any()


@pytest.mark.parametrize("seed", range(6))
def test_loop_equals_scan_over_seeds(seed):
    _assert_same_placements(_args(
        100 + seed, n=128, k_pad=64, k=33 + 5 * seed, s=2, targets=True, p=1,
        penalties=True, spread_alg=bool(seed % 2)))


def test_a_full_bucket_runs_every_step():
    args = _args(3, k_pad=32, k=32)
    assert scan_steps(args[9]) == 32
    assert _assert_same_placements(args)[1].all()


def test_one_placement():
    args = _args(4, k_pad=1, k=1)
    got = _assert_same_placements(args)
    assert got[1].tolist() == [True]


def test_no_active_row_runs_no_step():
    args = _args(5, k=0)
    assert scan_steps(args[9]) == 0
    got = _assert_same_placements(args)
    assert not got[1].any() and not got[0].any()


def test_a_non_prefix_mask_places_its_active_rows_only():
    active = np.zeros(32, bool)
    active[[0, 1, 4, 5, 6, 11, 17]] = True
    args = _args(6, active=active, s=1, p=1)
    assert scan_steps(active) == 18
    got = _assert_same_placements(args)
    assert got[1][active].all() and got[1].sum() == 7


def test_a_fleet_where_no_node_fits():
    args = list(_args(8, s=1))
    args[5] = np.zeros_like(args[5])          # nothing feasible
    got = _assert_same_placements(tuple(args))
    assert not got[1].any()
    assert (got[2] == F32(NEG)).all()


def test_a_fleet_that_fills_up_mid_loop():
    # a node takes one placement at most: found flips to False once
    # every node that fits has its one
    args = _args(9, n=12, k_pad=16, k=12, s=1, v=2, fit=1.0)
    got = _assert_same_placements(args)
    available, used0, ask, feasible = args[0], args[1], args[4], args[5]
    placed = int((feasible & (used0 + ask <= available).all(axis=1)).sum())
    assert 0 < placed < 12
    assert got[1][:placed].all() and not got[1][placed:].any()
    assert len(set(got[0][:placed].tolist())) == placed


def test_a_property_cap_that_binds_mid_loop():
    args = _args(10, s=0, p=1, limit=3)
    got = _assert_same_placements(args)
    assert got[1].any() and not got[1][:20].all()


@pytest.mark.parametrize("spread", ["s1_targets", "s2_mixed"])
def test_score_nodes_given_the_carried_counts_equals_its_own_lookup(spread):
    """score_nodes' three optional arguments are its tables read at each
    node's own value: passing them moves no bit, so the callers that
    leave them out (the count solve, score_nodes_once) and the loop that
    carries them run one formula."""
    from nomad_tpu.tensor.kernels import score_nodes

    (available, used, ptg, pjob, ask, feasible, aff, dev_aff, pen, _,
     val_id, val_ok, counts, desired, has_targets, weight, dp_id, dp_ok,
     dp_counts, dp_limit, lowest, tg_count, dh_job, dh_tg, alg, _
     ) = _args(11, p=1, penalties=True, **SPREADS[spread])
    kw = dict(
        available=available, used=used, ask=ask, feasible=feasible,
        placed_tg=ptg, placed_job=pjob, affinity_boost=aff,
        dev_affinity=dev_aff, penalty_idx=pen[0], spread_val_id=val_id,
        spread_val_ok=val_ok, spread_counts=counts, spread_desired=desired,
        spread_has_targets=has_targets, spread_weight=weight,
        dp_val_id=dp_id, dp_val_ok=dp_ok, dp_counts=dp_counts,
        dp_limit=dp_limit, lowest_boost=lowest, tg_count=tg_count,
        dh_job=dh_job, dh_tg=dh_tg, spread_alg=alg)
    at = dict(
        spread_counts_at=np.take_along_axis(counts, val_id, axis=1),
        spread_desired_at=np.take_along_axis(desired, val_id, axis=1),
        dp_counts_at=np.take_along_axis(dp_counts, dp_id, axis=1))
    for want, got in zip(jax.jit(lambda: score_nodes(**kw))(),
                         jax.jit(lambda: score_nodes(**kw, **at))()):
        assert np.array_equal(_bits(want), _bits(got))


def test_scan_steps_is_the_last_active_row_plus_one():
    assert scan_steps(np.zeros(8, bool)) == 0
    assert scan_steps(np.array([1, 1, 1, 0], bool)) == 3
    assert scan_steps(np.array([0, 0, 1, 0, 0, 1, 0, 0], bool)) == 6
    assert scan_steps(np.ones(4, bool)) == 4


# ---------------------------------------------------------------------------
# one program a bucket, and the placer's counters
# ---------------------------------------------------------------------------


def test_one_compiled_program_serves_every_count_of_a_bucket():
    from nomad_tpu.tensor.kernels import (pack_solve_args,
                                          solve_task_group_fused)

    solve_task_group_fused.clear_cache()
    for k in (257, 300, 511, 512):
        a = _args(12, n=64, k_pad=512, k=k, s=1, fit=40.0)
        packed = pack_solve_args(
            a[0], *a[2:7], *a[8:16], *a[20:25], dev_affinity=a[7],
            dp_val_id=a[16], dp_val_ok=a[17], dp_counts0=a[18],
            dp_limit=a[19], tie_perm=a[25])
        out = np.asarray(solve_task_group_fused(a[1], *packed))
        want = [np.asarray(x) for x in _ref_solve_task_group(*a)]
        assert out.shape == (3, 512)
        assert np.array_equal(out[0, :k], want[0][:k].astype(F32))
        assert np.array_equal(out[1, :k] > 0.5, want[1][:k])
        assert np.array_equal(_bits(out[2, :k]), _bits(want[2][:k]))
        assert not out[1, k:].any()
    assert solve_task_group_fused._cache_size() == 1


def test_the_placer_counts_steps_run_and_steps_padded():
    from nomad_tpu import mock
    from nomad_tpu.core.metrics import REGISTRY
    from nomad_tpu.structs import Spread, enums
    from nomad_tpu.structs.operator import SchedulerConfiguration
    from nomad_tpu.testing import Harness

    h = Harness()
    for i in range(400):
        n = mock.node()
        n.meta["rack"] = f"r{i % 5}"
        n.compute_class()
        h.store.upsert_node(n)
    job = mock.job()
    job.task_groups[0].count = 300
    job.task_groups[0].spreads = [Spread(attribute="${meta.rack}", weight=50)]
    h.store.upsert_job(job)
    run = REGISTRY.get("nomad.placer.scan_steps")
    padded = REGISTRY.get("nomad.placer.scan_steps_padded")
    h.process(mock.eval_for(job), sched_config=SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK))
    assert len(h.store.snapshot().allocs_by_job(job.id)) == 300
    assert REGISTRY.get("nomad.placer.scan_steps") == run + 300
    assert REGISTRY.get("nomad.placer.scan_steps_padded") == padded + 512
