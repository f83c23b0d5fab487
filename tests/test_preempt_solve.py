"""In-kernel preemption (kernels.preempt_solve): randomized parity
against the numpy host mirror, semantic invariants (no double-claimed
victims, deficit coverage), agreement with the exact host scanner,
victim-column construction, the evict-budget arm of solve_batch and its
sharded twin, the fitted restart portfolio regression, and the e2e
placer paths (mirror + device, warm no-retrace)."""

import random
from copy import deepcopy

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.state import StateStore
from nomad_tpu.structs import enums
from nomad_tpu.structs.operator import PreemptionConfig, SchedulerConfiguration
from nomad_tpu.structs.resources import Resources
from nomad_tpu.testing import Harness


# --------------------------------------------------------------------------
# randomized kernel-vs-mirror parity
# --------------------------------------------------------------------------

def _random_victim_problem(seed, n=24, k=12, v=8, d=3):
    """Integer-valued f32 inputs (< 2^24, exact in both f32 and f64) in
    the shape build_victim_tensors emits: victim columns pre-sorted
    priority-ascending, high-fill usage so most rows need evictions."""
    rng = np.random.default_rng(seed)
    available = rng.integers(2000, 16000, (n, d)).astype(np.float32)
    used = np.floor(available * rng.uniform(0.7, 1.05, (n, d))).astype(
        np.float32)
    ask = rng.integers(200, 1500, d).astype(np.float32)
    feasible = rng.random(n) > 0.2
    active = rng.random(k) > 0.1
    v_prio = np.zeros((n, v), np.float32)
    v_vec = np.zeros((n, v, d), np.float32)
    v_elig = np.zeros((n, v), bool)
    v_flag = np.zeros((n, v), bool)
    for i in range(n):
        cnt = int(rng.integers(0, v + 1))
        prios = np.sort(rng.integers(1, 60, cnt))
        for j in range(cnt):
            v_prio[i, j] = prios[j]
            v_vec[i, j] = rng.integers(50, 900, d)
            v_elig[i, j] = True
            v_flag[i, j] = rng.random() < 0.15
    max_p = v_prio.max(axis=1)
    net_prio = np.where(
        max_p > 0,
        max_p + v_prio.sum(axis=1) / np.maximum(max_p, 1.0),
        0.0).astype(np.float32)
    return (available, used, ask, feasible, net_prio, active,
            v_prio, v_vec, v_elig, v_flag)


@pytest.mark.parametrize("seed", range(8))
def test_kernel_matches_host_mirror(seed):
    """preempt_solve must agree with _preempt_solve_host bit-exactly on
    picks, victim sets, and flags — the mirror is both the small-shape
    production path and the parity oracle the placer revalidates
    against, so any drift is a correctness bug."""
    import jax

    from nomad_tpu.tensor.kernels import preempt_solve
    from nomad_tpu.tensor.placer import _preempt_solve_host

    args = _random_victim_problem(seed)
    picks_h, victims_h, flagged_h, scores_h = _preempt_solve_host(*args)
    out = jax.device_get(preempt_solve(*jax.device_put(args)))
    picks_k, victims_k, flagged_k, scores_k = out

    np.testing.assert_array_equal(np.asarray(picks_k), picks_h)
    np.testing.assert_array_equal(np.asarray(victims_k), victims_h)
    np.testing.assert_array_equal(np.asarray(flagged_k), flagged_h)
    live = picks_h >= 0
    np.testing.assert_allclose(np.asarray(scores_k)[live], scores_h[live],
                               rtol=1e-5)


@pytest.mark.parametrize("seed", range(8))
def test_victim_selection_invariants(seed):
    """Semantic invariants of the carry, independent of the mirror:
    no victim is claimed by two sibling requests in one launch, every
    selected victim was eligible, and each placement's victim prefix
    covers its deficit in every resource dim (replayed request by
    request against the committed usage)."""
    (available, used, ask, feasible, net_prio, active,
     v_prio, v_vec, v_elig, v_flag) = _random_victim_problem(seed, n=16, k=16)
    from nomad_tpu.tensor.placer import _preempt_solve_host

    picks, victims, flagged, _ = _preempt_solve_host(
        available, used, ask, feasible, net_prio, active,
        v_prio, v_vec, v_elig, v_flag)

    claimed = np.zeros(v_elig.shape, dtype=bool)
    run_used = used.astype(np.float64).copy()
    for i in range(len(picks)):
        b = picks[i]
        if b < 0:
            assert not victims[i].any()
            continue
        assert active[i] and feasible[b]
        sel = victims[i]
        # only eligible, never previously claimed columns
        assert not (sel & ~v_elig[b]).any()
        assert not (sel & claimed[b]).any()
        claimed[b] |= sel
        deficit = np.maximum(run_used[b] + ask - available[b], 0.0)
        evicted = (v_vec[b] * sel[:, None]).sum(axis=0)
        if deficit.max() > 0.0:
            assert (evicted >= deficit).all(), (i, deficit, evicted)
        run_used[b] = np.maximum(run_used[b] + ask - evicted, 0.0)
        assert (run_used[b] <= available[b]).all()


def test_victim_prefix_is_priority_ascending():
    """Victims come off the column as a priority-ascending prefix of
    the still-unclaimed entries — never a higher-priority victim while
    a lower-priority one stays unselected."""
    (available, used, ask, feasible, net_prio, active,
     v_prio, v_vec, v_elig, v_flag) = _random_victim_problem(11, n=8, k=10)
    from nomad_tpu.tensor.placer import _preempt_solve_host

    picks, victims, _, _ = _preempt_solve_host(
        available, used, ask, feasible, net_prio, active,
        v_prio, v_vec, v_elig, v_flag)

    claimed = np.zeros(v_elig.shape, dtype=bool)
    for i in range(len(picks)):
        b = picks[i]
        if b < 0:
            continue
        row = v_elig[b] & ~claimed[b]
        sel = victims[i]
        idx = np.flatnonzero(row)
        sel_in_row = sel[idx]
        # within the available column the selection is a prefix
        if sel_in_row.any():
            last = int(np.flatnonzero(sel_in_row).max())
            assert sel_in_row[: last + 1].all()
        claimed[b] |= sel


# --------------------------------------------------------------------------
# eligibility + victim columns vs scheduler.preemption
# --------------------------------------------------------------------------

def _filled_node(store, cpu=4000, mem=8192):
    n = mock.node()
    n.resources.cpu = cpu
    n.resources.memory_mb = mem
    n.compute_class()
    store.upsert_node(n)
    return n


def _alloc_at(store, node, prio, cpu, mem, aid=None):
    j = mock.batch_job()
    j.priority = prio
    j.task_groups[0].tasks[0].resources = Resources(cpu=cpu, memory_mb=mem)
    store.upsert_job(j)
    a = mock.alloc(j, node)
    if aid is not None:
        a.id = aid
    a.allocated_vec = Resources(cpu=cpu, memory_mb=mem).vec()
    store.upsert_allocs([a])
    return a


def test_victim_candidates_delta_edge_and_order():
    """Eligibility is current_priority - victim >= PRIORITY_DELTA (10),
    and the canonical column order is (priority asc, alloc id asc) —
    the order the kernel's prefix rule assumes."""
    from nomad_tpu.scheduler.preemption import victim_candidates

    store = StateStore()
    node = _filled_node(store)
    edge = _alloc_at(store, node, prio=40, cpu=100, mem=64, aid="b-edge")
    _alloc_at(store, node, prio=41, cpu=100, mem=64, aid="c-over")
    low_b = _alloc_at(store, node, prio=10, cpu=100, mem=64, aid="b-low")
    low_a = _alloc_at(store, node, prio=10, cpu=100, mem=64, aid="a-low")

    snap = store.snapshot()
    ctx = EvalContext(snap, eval_id="e-vc")
    cands = victim_candidates(ctx.proposed_allocs(node.id), 50)
    assert [a.id for a in cands] == [low_a.id, low_b.id, edge.id]


def test_build_victim_tensors_mirrors_candidates():
    """The padded victim columns reproduce victim_candidates per node:
    same order, eligibility flags, exact-resource flags, and the
    evictable-capacity aggregate the node score consumes."""
    from nomad_tpu.scheduler.preemption import (
        victim_candidates, victim_holds_exact_resources)
    from nomad_tpu.tensor.cluster import ClusterTensors, build_victim_tensors

    store = StateStore()
    nodes = [_filled_node(store) for _ in range(3)]
    _alloc_at(store, nodes[0], prio=20, cpu=300, mem=256)
    _alloc_at(store, nodes[0], prio=10, cpu=500, mem=128)
    # committed rows are shared MVCC history: copy before mutating
    ported = deepcopy(_alloc_at(store, nodes[1], prio=15, cpu=200, mem=64))
    ported.allocated_ports = {"http": 8080}
    store.upsert_allocs([ported])
    # node 2 stays empty

    snap = store.snapshot()
    ctx = EvalContext(snap, eval_id="e-bt")
    cluster = ClusterTensors.build(ctx, nodes)
    vt = build_victim_tensors(ctx, cluster, current_priority=50)

    for i, node in enumerate(nodes):
        cands = victim_candidates(ctx.proposed_allocs(node.id), 50)
        assert [a.id for a in vt.refs[i]] == [a.id for a in cands]
        assert vt.elig[i].sum() == len(cands)
        d = cluster.available.shape[1]
        expect_ev = np.zeros(d, np.float32)
        for v, a in enumerate(cands):
            assert vt.prio[i, v] == a.job.priority
            np.testing.assert_array_equal(
                vt.vec[i, v], np.asarray(a.allocated_vec[:d], np.float32))
            assert vt.flagged[i, v] == victim_holds_exact_resources(a)
            expect_ev += np.asarray(a.allocated_vec[:d], np.float32)
        np.testing.assert_array_equal(vt.evictable[i], expect_ev)
    assert not vt.elig[2].any()
    assert vt.net_prio[2] == 0.0


def test_mirror_agrees_with_exact_scanner():
    """Single node, distinct-priority equal-size victims: the kernel's
    priority-ascending prefix must pick exactly the set the exact host
    scanner (preempt_for_task_group) evicts."""
    from nomad_tpu.scheduler.preemption import preempt_for_task_group
    from nomad_tpu.tensor.cluster import ClusterTensors, build_victim_tensors
    from nomad_tpu.tensor.placer import _preempt_solve_host

    store = StateStore()
    node = _filled_node(store, cpu=4000, mem=8192)
    for prio in (10, 20, 30, 40):
        _alloc_at(store, node, prio=prio, cpu=1000, mem=512)

    snap = store.snapshot()
    ctx = EvalContext(snap, eval_id="e-sc")
    cluster = ClusterTensors.build(ctx, [node])
    vt = build_victim_tensors(ctx, cluster, current_priority=50)
    d = cluster.available.shape[1]

    ask_vec = np.asarray(Resources(cpu=2500, memory_mb=256).vec(),
                         np.float64)
    feas = np.zeros(cluster.n_pad, bool)
    feas[0] = True
    picks, victims, flagged, _ = _preempt_solve_host(
        cluster.available, cluster.used, ask_vec[:d].astype(np.float32),
        feas, vt.net_prio, np.array([True]),
        vt.prio, vt.vec, vt.elig, vt.flagged)
    assert picks[0] == 0 and not flagged[0]
    kernel_ids = {vt.refs[0][v].id for v in np.flatnonzero(victims[0])}

    exact = preempt_for_task_group(
        node, ctx.proposed_allocs(node.id), ask_vec, 50)
    assert exact, "exact scanner found no victims"
    assert {a.id for a in exact} == kernel_ids
    # deficit 2500 over three 1000-cpu victims -> the three lowest prios
    assert sorted(a.job.priority for a in exact) == [10, 20, 30]


# --------------------------------------------------------------------------
# e2e: placer preemption paths (mirror + device, warm no-retrace)
# --------------------------------------------------------------------------

def _preempt_config():
    return SchedulerConfiguration(
        scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK,
        preemption_config=PreemptionConfig(batch_scheduler_enabled=True))


def _sized_batch_job(count, cpu, mem, prio):
    j = mock.batch_job()
    j.priority = prio
    tg = j.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    return j


def _run_preempt_scenario(n_nodes=16, hi_count=32):
    """16 full nodes (2 low-prio fillers each), then a high-prio batch
    that only fits by evicting fillers — returns the placer stats delta
    and the final snapshot."""
    from nomad_tpu.structs import allocs_fit
    from nomad_tpu.tensor.placer import preempt_stats

    h = Harness()
    for _ in range(n_nodes):
        n = mock.node()
        n.resources.cpu = 4000
        n.resources.memory_mb = 8192
        n.compute_class()
        h.store.upsert_node(n)
    filler = _sized_batch_job(2 * n_nodes, cpu=1900, mem=3800, prio=20)
    h.store.upsert_job(filler)
    h.process(mock.eval_for(filler), sched_config=_preempt_config())
    snap = h.store.snapshot()
    placed_fill = [a for a in snap.allocs_by_job(filler.id)
                   if not a.terminal_status()]
    assert len(placed_fill) == 2 * n_nodes

    hi = _sized_batch_job(hi_count, cpu=1000, mem=2000, prio=80)
    h.store.upsert_job(hi)
    before = preempt_stats()
    h.process(mock.eval_for(hi), sched_config=_preempt_config())
    after = preempt_stats()
    delta = {k: after[k] - before[k] for k in after}

    snap = h.store.snapshot()
    hi_placed = [a for a in snap.allocs_by_job(hi.id)
                 if not a.terminal_status()]
    evicted = [a for a in snap.allocs_by_job(filler.id)
               if a.desired_status == enums.ALLOC_DESIRED_EVICT]
    for n in snap.nodes():
        live = [a for a in snap.allocs_by_node(n.id)
                if not a.terminal_status()]
        fit, dim, _ = allocs_fit(n, live)
        assert fit, (n.id, dim)
    return delta, hi_placed, evicted


def test_e2e_mirror_path_no_host_rows():
    """Small shapes route through the numpy mirror; every preempted
    placement (the scan's unplaced rows) must resolve from the kernel
    columns (host_preempted == 0 — victims hold no ports/devices here),
    victims are unique, and capacity holds after the wave."""
    delta, hi_placed, evicted = _run_preempt_scenario()
    assert len(hi_placed) == 32
    assert delta["kernel_preempted"] >= 1
    assert delta["host_preempted"] == 0
    assert delta["victim_parity_checked"] >= delta["kernel_preempted"]
    assert evicted and len({a.id for a in evicted}) == len(evicted)


def test_e2e_device_path_warm_no_retrace():
    """With PREEMPT_DEVICE_MIN forced to 0 the same scenario runs the
    jitted kernel; a second run at identical shapes goes through the
    no_retrace warm window and must not grow the jit cache (the
    numpy-vs-device_put cache-fork regression)."""
    from nomad_tpu.tensor.kernels import preempt_solve
    from nomad_tpu.tensor.placer import TPUPlacer

    old_min = TPUPlacer.PREEMPT_DEVICE_MIN
    TPUPlacer.PREEMPT_DEVICE_MIN = 0
    try:
        delta, hi_placed, _ = _run_preempt_scenario()
        assert len(hi_placed) == 32
        assert delta["kernel_preempted"] >= 1
        assert delta["host_preempted"] == 0
        warm_size = preempt_solve._cache_size()
        # identical shapes again: inside the no_retrace window now
        delta2, hi_placed2, _ = _run_preempt_scenario()
        assert len(hi_placed2) == 32
        assert delta2["host_preempted"] == 0
        assert preempt_solve._cache_size() == warm_size
    finally:
        TPUPlacer.PREEMPT_DEVICE_MIN = old_min


# --------------------------------------------------------------------------
# solve_batch evict-budget arm + sharded twin
# --------------------------------------------------------------------------

def _batch_problem(seed, n=32, g=4):
    rng = np.random.default_rng(seed)
    d = 4
    avail = np.zeros((n, d), np.float32)
    avail[:, 0] = rng.choice([4000, 8000, 16000], n)
    avail[:, 1] = rng.choice([8192, 16384, 32768], n)
    avail[:, 2] = 100_000
    avail[:, 3] = 1000
    used0 = np.zeros((n, d), np.float32)
    used0[:, 0] = rng.integers(0, 2000, n)
    used0[:, 1] = rng.integers(0, 4000, n)
    feas = rng.random((g, n)) > 0.25
    aff = np.where(rng.random((g, n)) > 0.7, 0.3, 0.0).astype(np.float32)
    ask = np.zeros((g, d), np.float32)
    ask[:, 0] = rng.integers(50, 400, g)
    ask[:, 1] = rng.integers(32, 512, g)
    k = rng.integers(10, 100, g).astype(np.int32)
    seeds = rng.integers(0, 2**31, g).astype(np.uint32)
    return avail, used0, feas, aff, ask, k, seeds


def _call_solve_batch(avail, used0, feas, aff, ask, k, seeds,
                      evict=None, net_prio=None):
    import jax.numpy as jnp

    from nomad_tpu.tensor.batch_solver import solve_batch

    g, d = ask.shape
    cidx = np.zeros(1, np.int32)
    cdelta = np.zeros((1, d), np.float32)
    kw = {}
    if evict is not None:
        kw = dict(evict=jnp.asarray(evict), net_prio=jnp.asarray(net_prio))
    return solve_batch(
        jnp.asarray(used0), jnp.asarray(avail), jnp.asarray(feas),
        jnp.asarray(aff), jnp.asarray(ask), jnp.asarray(k),
        jnp.asarray(k.astype(np.float32)), jnp.asarray(seeds),
        jnp.asarray(cidx), jnp.asarray(cdelta), g=g, **kw)


def test_solve_batch_evict_budget_enables_placement():
    """On a saturated cluster the victim-blind graph places nothing;
    handing the auction arm the evictable-capacity columns lets it bid
    over victim budgets, and the greedy safety arm stays victim-blind
    (zero placements) by design."""
    rng = np.random.default_rng(5)
    n, g, d = 16, 3, 4
    avail = np.full((n, d), 8000, np.float32)
    avail[:, 2:] = 100_000
    used0 = avail.copy()  # saturated
    feas = np.ones((g, n), bool)
    aff = np.zeros((g, n), np.float32)
    ask = np.zeros((g, d), np.float32)
    ask[:, 0] = 500
    ask[:, 1] = 500
    k = np.full(g, 8, np.int32)
    seeds = rng.integers(0, 2**31, g).astype(np.uint32)

    _, counts_blind, _ = _call_solve_batch(
        avail, used0, feas, aff, ask, k, seeds)
    assert int(np.asarray(counts_blind).sum()) == 0

    evict = np.zeros((n, d), np.float32)
    evict[:, 0] = 4000
    evict[:, 1] = 4000
    net_prio = np.full(n, 25.0, np.float32)
    used_e, counts_e, info_e = _call_solve_batch(
        avail, used0, feas, aff, ask, k, seeds,
        evict=evict, net_prio=net_prio)
    counts_e = np.asarray(counts_e)
    info_e = np.asarray(info_e)
    assert int(counts_e.sum()) == int(3 * 8)
    assert info_e[5] > 0.5 and int(info_e[3]) == 0
    # placements never exceed capacity + victim budget on any node
    assert (np.asarray(used_e) <= avail + evict + 1e-3).all()


@pytest.mark.parametrize("seed", range(4))
def test_solve_batch_zero_evict_matches_legacy_graph(seed):
    """evict=0 / net_prio huge (pscore ~ 0) must reproduce the
    victim-blind graph's counts exactly: the budget arm degenerates to
    the legacy bid surface when there is nothing to reclaim."""
    avail, used0, feas, aff, ask, k, seeds = _batch_problem(seed)
    n, d = avail.shape
    _, counts_a, info_a = _call_solve_batch(
        avail, used0, feas, aff, ask, k, seeds)
    _, counts_b, info_b = _call_solve_batch(
        avail, used0, feas, aff, ask, k, seeds,
        evict=np.zeros((n, d), np.float32),
        net_prio=np.full(n, 1.0e7, np.float32))
    np.testing.assert_array_equal(np.asarray(counts_a),
                                  np.asarray(counts_b))
    np.testing.assert_array_equal(np.asarray(info_a)[2:4],
                                  np.asarray(info_b)[2:4])


def test_sharded_twin_parity_with_victim_columns():
    """The mesh-sharded solve_batch twin must agree bit-exactly on
    counts with the single-device kernel WITH nonzero victim budgets
    riding the node axis (satellite: sharded-twin bit-exactness)."""
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (conftest sets 8 virtual)")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nomad_tpu.tensor.sharding import make_solve_batch_sharded, node_mesh

    rng = np.random.default_rng(13)
    avail, used0, feas, aff, ask, k, seeds = _batch_problem(13, n=64, g=8)
    n, d = avail.shape
    used0[:, 0] = avail[:, 0] - 100.0  # tight: budgets decide placements
    used0[:, 1] = avail[:, 1] - 128.0
    evict = np.zeros((n, d), np.float32)
    evict[:, 0] = rng.choice([0, 2000, 4000], n)
    evict[:, 1] = rng.choice([0, 2048], n)
    net_prio = rng.uniform(10.0, 60.0, n).astype(np.float32)
    g = feas.shape[0]
    cidx = np.array([0, 5], np.int32)
    cdelta = np.zeros((2, d), np.float32)
    cdelta[0, 0] = 300.0

    from nomad_tpu.tensor.batch_solver import solve_batch

    used_1, counts_1, info_1 = solve_batch(
        jnp.asarray(used0), jnp.asarray(avail), jnp.asarray(feas),
        jnp.asarray(aff), jnp.asarray(ask), jnp.asarray(k),
        jnp.asarray(k.astype(np.float32)), jnp.asarray(seeds),
        jnp.asarray(cidx), jnp.asarray(cdelta),
        evict=jnp.asarray(evict), net_prio=jnp.asarray(net_prio), g=g)
    assert int(np.asarray(counts_1).sum()) > 0

    mesh = node_mesh()
    solve_sh = make_solve_batch_sharded(mesh)
    sh = NamedSharding(mesh, P("nodes", None))
    used_m, counts_m, info_m, _ = solve_sh(
        jax.device_put(used0, sh), jax.device_put(avail, sh),
        jnp.asarray(feas), jnp.asarray(aff), jnp.asarray(ask),
        jnp.asarray(k), jnp.asarray(seeds), jnp.asarray(cidx),
        jnp.asarray(cdelta), jax.device_put(evict, sh),
        jax.device_put(net_prio, NamedSharding(mesh, P("nodes"))), g=g)

    np.testing.assert_array_equal(np.asarray(counts_m),
                                  np.asarray(counts_1))
    np.testing.assert_allclose(np.asarray(used_m), np.asarray(used_1),
                               atol=1e-2)
    np.testing.assert_array_equal(np.asarray(info_m)[2:4],
                                  np.asarray(info_1)[2:4])
    np.testing.assert_allclose(np.asarray(info_m)[:2],
                               np.asarray(info_1)[:2], rtol=1e-4)


# --------------------------------------------------------------------------
# fitted restart portfolio regression
# --------------------------------------------------------------------------

def _portfolio_arm(used0, avail, feas, aff, ask, k, seeds, t, jscale,
                   ptemp, g):
    """One auction restart exactly as solve_batch's unrolled loop draws
    it (fold_in(t) jitter stream, temperature-scaled price bump) —
    the scripts/fit_portfolio.py replay harness."""
    import jax
    import jax.numpy as jnp

    from nomad_tpu.tensor.batch_solver import (
        MAX_ROUNDS, PRICE_EPS, _auction, _packing_score_xp)
    from nomad_tpu.tensor.kernels import TIE_JITTER

    n = avail.shape[0]
    jits = jax.vmap(
        lambda s: jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(s), t), (n,),
            jnp.float32, 0.0, TIE_JITTER * jscale))(seeds)
    used_t, take_t, _ = _auction(used0, avail, feas, aff, ask, k, jits, g,
                                 MAX_ROUNDS, price_eps=PRICE_EPS * ptemp)
    return (int(take_t.sum()),
            float(_packing_score_xp(jnp, take_t, avail, used_t)))


def _contended_problem(seed, n=64, g=8):
    """The fit regime: near-full heterogeneous cluster, demand above
    capacity (under low fill every portfolio places everything and the
    comparison is moot)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    d = 3
    available = rng.integers(4000, 32000, (n, d)).astype(np.float32)
    used0 = (available * rng.uniform(0.55, 0.95, (n, d))).astype(np.float32)
    feas = rng.random((g, n)) > 0.25
    aff = np.where(rng.random((g, n)) > 0.8,
                   rng.uniform(-0.5, 0.5, (g, n)), 0.0).astype(np.float32)
    ask = rng.integers(100, 1500, (g, d)).astype(np.float32)
    k = rng.integers(16, 128, g).astype(np.int32)
    seeds = rng.integers(0, 2**31, g).astype(np.uint32)
    return (jnp.asarray(available), jnp.asarray(used0), jnp.asarray(feas),
            jnp.asarray(aff), jnp.asarray(ask), jnp.asarray(k),
            jnp.asarray(seeds))


def _best_of(portfolio, prob):
    import jax.numpy as jnp

    avail, used0, feas, aff, ask, k, seeds = prob
    g = int(feas.shape[0])
    best = None
    for t, (js, pt) in enumerate(portfolio):
        cand = _portfolio_arm(used0, avail, feas, aff, ask, k, seeds,
                              jnp.uint32(t), jnp.float32(js),
                              jnp.float32(pt), g)
        if best is None or cand > best:
            best = cand
    return best


def test_portfolio_structure():
    """The frozen constants keep their contract: 5 restarts, the legacy
    (1.0, 1.0) basin pinned at slot 0 (the safety arm the fit started
    from)."""
    from nomad_tpu.tensor.batch_solver import PORTFOLIO, RESTARTS

    assert RESTARTS == len(PORTFOLIO) == 5
    assert PORTFOLIO[0] == (1.0, 1.0)


@pytest.mark.parametrize("seed", [3, 5, 12, 17])
def test_fitted_portfolio_beats_legacy_at_equal_restarts(seed):
    """Regression for the offline fit: at EQUAL restart count the
    fitted portfolio's lexicographic (placed, packing score) must
    strictly beat five identical legacy (1.0, 1.0) restarts on these
    pinned contended seeds (measured wins of the fit; a tie here means
    the fitted constants regressed). The list is pinned against the
    installed JAX's random stream (0.9.0): each seed here wins by at
    least one more placement, not by a score digit."""
    from nomad_tpu.tensor.batch_solver import PORTFOLIO

    prob = _contended_problem(seed)
    assert _best_of(PORTFOLIO, prob) > _best_of(((1.0, 1.0),) * 5, prob)


@pytest.mark.parametrize("seed", [0, 9, 19])
def test_fitted_portfolio_never_loses_to_legacy(seed):
    """On seeds where the fit finds no edge it must still never fall
    below the legacy basin — slot 0 IS the legacy arm, so best-of can
    only tie or win."""
    from nomad_tpu.tensor.batch_solver import PORTFOLIO

    prob = _contended_problem(seed)
    assert _best_of(PORTFOLIO, prob) >= _best_of(((1.0, 1.0),) * 5, prob)


def test_solve_batch_selection_dominates_greedy():
    """The portfolio pick inside one solve_batch launch returns
    whichever arm wins (total placed, packing score) — the selected
    assignment never loses to the greedy chain run from the same
    start state."""
    for seed in range(3):
        avail, used0, feas, aff, ask, k, seeds = _batch_problem(seed)
        _, counts, info = _call_solve_batch(
            avail, used0, feas, aff, ask, k, seeds)
        info = np.asarray(info)
        sel_placed = info[2] if info[5] > 0.5 else info[3]
        sel_score = info[0] if info[5] > 0.5 else info[1]
        assert (sel_placed, sel_score) >= (info[3], info[1])
        assert int(np.asarray(counts).sum()) == int(sel_placed)


# --------------------------------------------------------------------------
# modelcheck: solve_batch scenario
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(3))
def test_modelcheck_solve_batch_scenario(seed):
    """The interleaving-exploring checker's solve_batch scenario (joint
    tier rendezvous + ledger handshake) must hold under random
    schedules."""
    from nomad_tpu.analysis import modelcheck as mc

    r = mc.run_scenario("solve_batch", seed=seed)
    assert r.ok, r.render()
