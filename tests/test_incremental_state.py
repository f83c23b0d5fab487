"""Tier-1 gate for nomadstate (tensor/incremental.py): the
device-resident incremental cluster state.

Contracts pinned here:
- a randomized Allocation delta stream folded incrementally is
  bit-exact against gen-bounded snapshot rebuilds (integral resource
  vectors make f64 adds commute exactly — no tolerance anywhere);
- columnar AllocBlock expansion, promoted-row override and GC pops
  follow the store's semantics (shared with analysis/shadow.py via
  state/deltas.py);
- ring truncation / the restore sentinel force a full resync, never
  incremental patching;
- a store with no feed attached gets the exact full build;
- the sharded scatter twin is bit-exact against the single-device
  scatter, and device twins flush to exactly base.astype(f32);
- NodeSlotRegistry keeps node→slot identity stable and recycles slots
  of deleted nodes lowest-first;
- a seeded divergence trips the parity digest and the feed repairs by
  resync instead of wedging.
"""

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.core.events import EventBroker
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.state import StateStore
from nomad_tpu.state.persist import dump_store, restore_store
from nomad_tpu.structs import enums
from nomad_tpu.structs.alloc import AllocBlock, Allocation
from nomad_tpu.structs.resources import RESOURCE_DIMS
from nomad_tpu.tensor.cluster import ClusterStatic, ClusterTensors, NodeSlotRegistry
from nomad_tpu.tensor.incremental import StateTracker, feed_for
from nomad_tpu.tensor.overlay import INFLIGHT


@pytest.fixture
def tracked():
    """A private tracker over a fresh (store, broker) pair. install()
    arms the periodic parity digests; feeds attach regardless (they are
    production features, not sanitizer-only)."""
    store = StateStore()
    broker = EventBroker(store)
    tracker = StateTracker()
    tracker.install()
    feed = tracker.attach(store, broker)
    try:
        yield store, broker, tracker, feed
    finally:
        tracker.uninstall()


def _alloc(aid, nid, cpu, mem):
    a = Allocation(id=aid, node_id=nid, job_id="ij", eval_id="ie")
    vec = np.zeros_like(a.allocated_vec)
    vec[0] = float(cpu)
    vec[1] = float(mem)
    a.allocated_vec = vec
    return a


def _static_over(store, n_nodes):
    nodes = []
    for _ in range(n_nodes):
        n = mock.node()
        n.compute_class()
        store.upsert_node(n)
        nodes.append(n)
    return nodes, ClusterStatic(nodes)


def _truth(store, static):
    """Gen-bounded per-node usage gather — the parity oracle."""
    out = np.zeros((static.n_pad, RESOURCE_DIMS))
    gen = store._index
    for nid, i in static.node_index.items():
        vec = store._node_usage.get(nid, gen)
        if vec is not None:
            out[i] = vec[:RESOURCE_DIMS]
    return out


def test_randomized_delta_stream_is_bit_exact(tracked):
    store, _, tracker, feed = tracked
    rng = np.random.default_rng(7)
    nodes, static = _static_over(store, 6)
    live = []
    serial = 0
    for round_i in range(60):
        op = rng.integers(0, 4)
        if op == 0 or not live:                     # place a new alloc
            serial += 1
            a = _alloc(f"ia{serial}", nodes[rng.integers(0, 6)].id,
                       int(rng.integers(1, 9)) * 100,
                       int(rng.integers(1, 9)) * 64)
            store.upsert_allocs([a])
            live.append(a.id)
        elif op == 1:                               # client-terminal
            aid = live.pop(int(rng.integers(0, len(live))))
            store.update_allocs_from_client([Allocation(
                id=aid, client_status=enums.ALLOC_CLIENT_COMPLETE)])
        elif op == 2:                               # annotation rewrite
            aid = live[int(rng.integers(0, len(live)))]
            cur = store.snapshot().alloc_by_id(aid)
            again = _alloc(aid, cur.node_id, 0, 0)
            again.allocated_vec = cur.allocated_vec.copy()
            store.upsert_allocs([again])
        else:                                       # GC the terminal set
            store.gc_terminal_allocs(before_index=store._index + 1)
        base = feed.base_for(static)
        assert base is not None
        assert np.array_equal(base, _truth(store, static))
        assert not base.flags.writeable             # shared view
    assert feed.force_verify()
    assert tracker.violations == []
    assert feed.stats()["deltas_applied"] > 0
    assert feed.stats()["fast_hits"] >= 59          # one cold resync only


def test_block_expansion_promotion_and_gc(tracked):
    store, _, tracker, feed = tracked
    nodes, static = _static_over(store, 4)
    assert feed.base_for(static) is not None        # epoch up before blocks
    job = mock.batch_job()
    job.task_groups[0].count = 8
    store.upsert_job(job)
    vec = np.zeros_like(mock.alloc(job, nodes[0]).allocated_vec)
    vec[0] = 50.0
    vec[1] = 32.0
    block = AllocBlock(
        id="blk-inc", eval_id="ev-inc", namespace=job.namespace,
        job_id=job.id, job=job, job_version=job.version,
        task_group=job.task_groups[0].name,
        name_indices=np.arange(8, dtype=np.int64),
        node_ids=[nodes[0].id, nodes[1].id],
        node_names=[nodes[0].name, nodes[1].name],
        counts=np.array([4, 4], dtype=np.int64),
        allocated_vec=vec,
    )
    store.upsert_plan_results([], alloc_blocks=[block], job=job)
    base = feed.base_for(static)
    assert np.array_equal(base, _truth(store, static))
    # promote one position into a real row (client-terminal): the row
    # event must override the block expansion exactly once
    target = store.snapshot().allocs_by_job(job.id)[0]
    store.update_allocs_from_client([Allocation(
        id=target.id, client_status=enums.ALLOC_CLIENT_COMPLETE)])
    base = feed.base_for(static)
    assert np.array_equal(base, _truth(store, static))
    # GC pops the promoted position; the held block ref compensates
    store.gc_terminal_allocs(before_index=store._index + 1)
    base = feed.base_for(static)
    assert np.array_equal(base, _truth(store, static))
    assert feed.force_verify()
    assert tracker.violations == []


def test_truncation_forces_resync(tracked):
    store, broker, tracker, feed = tracked
    nodes, static = _static_over(store, 3)
    store.upsert_allocs([_alloc("ia0", nodes[0].id, 200, 128)])
    assert feed.base_for(static) is not None
    before = feed.stats()["resyncs"]
    # operator restore truncates every ring: the contract answer is a
    # full snapshot rebuild, never incremental patching
    restore_store(store, dump_store(store))
    store.upsert_allocs([_alloc("ia1", nodes[1].id, 300, 64)])
    base = feed.base_for(static)
    assert np.array_equal(base, _truth(store, static))
    assert feed.stats()["resyncs"] > before
    assert feed.force_verify()
    assert tracker.violations == []


def test_membership_change_resyncs_same_layout_keeps_epoch(tracked):
    store, _, tracker, feed = tracked
    nodes, static = _static_over(store, 4)
    assert feed.base_for(static) is not None
    resyncs = feed.stats()["resyncs"]
    # same membership/order under a new static: the epoch survives
    twin = ClusterStatic(nodes)
    assert feed.base_for(twin) is not None
    assert feed.stats()["resyncs"] == resyncs
    # deleting an in-layout node marks the epoch stale -> resync
    store.delete_node(nodes[2].id)
    remaining = [n for n in nodes if n.id != nodes[2].id]
    shrunk = ClusterStatic(remaining)
    base = feed.base_for(shrunk)
    assert base is not None
    assert feed.stats()["resyncs"] > resyncs
    assert np.array_equal(base, _truth(store, shrunk))
    assert feed.force_verify()
    assert tracker.violations == []


def test_store_without_feed_gets_the_exact_full_build(tracked, monkeypatch):
    store, _, tracker, feed = tracked
    nodes, _ = _static_over(store, 5)
    for i in range(9):
        store.upsert_allocs([_alloc(f"ia{i}", nodes[i % 5].id,
                                    (i + 1) * 100, (i + 1) * 32)])
    INFLIGHT._entries.clear()       # deterministic fast path
    ctx = EvalContext(store.snapshot(), eval_id="inc-on")
    warm = ClusterTensors.build(ctx, nodes)
    assert warm._used_shared and not warm.used.flags.writeable
    # no feed on the store (as before maybe_attach, or on a bare
    # StateStore): the build gathers usage row by row
    monkeypatch.setattr(store, "_incremental_feed", None)
    assert feed_for(store) is None
    cold = ClusterTensors.build(
        EvalContext(store.snapshot(), eval_id="inc-off"), nodes)
    assert not cold._used_shared and cold.used.flags.writeable
    assert np.array_equal(np.asarray(warm.used), cold.used)
    monkeypatch.undo()
    # copy-on-write: a private view detaches from the shared base
    private = warm._ensure_private()
    assert private.flags.writeable and not warm._used_shared
    private[0] += 1.0
    assert not np.array_equal(private, cold.used)
    assert np.array_equal(feed.base_for(warm.static)[: len(nodes)],
                          cold.used[: len(nodes)])  # base untouched


def test_device_twin_flushes_to_exact_base(tracked):
    import jax

    store, _, tracker, feed = tracked
    nodes, static = _static_over(store, 4)
    store.upsert_allocs([_alloc("it0", nodes[0].id, 400, 256)])
    dev = feed.device_used(static)
    assert dev is not None
    base = feed.base_for(static)
    assert np.array_equal(np.asarray(jax.device_get(dev)),
                          np.asarray(base, dtype=np.float32))
    # pile on deltas, flush through the scatter, re-check exactness
    for i in range(6):
        store.upsert_allocs([_alloc(f"it{i + 1}", nodes[i % 4].id,
                                    (i + 1) * 50, 32)])
    dev = feed.device_used(static)
    base = feed.base_for(static)
    assert np.array_equal(np.asarray(jax.device_get(dev)),
                          np.asarray(base, dtype=np.float32))
    assert feed.force_verify()                      # twin parity included
    assert tracker.violations == []


def test_sharded_scatter_matches_single_device(eight_devices):
    import jax

    from nomad_tpu.tensor.incremental import _scatter_fn
    from nomad_tpu.tensor.sharding import make_state_scatter_sharded, node_mesh

    mesh = node_mesh(eight_devices)
    rng = np.random.default_rng(11)
    n_pad, d, k = 16, RESOURCE_DIMS, 8
    used = (rng.integers(0, 50, (n_pad, d)) * 1.0).astype(np.float32)
    idx = rng.integers(0, n_pad, k).astype(np.int32)
    delta = (rng.integers(-5, 6, (k, d)) * 1.0).astype(np.float32)

    single = np.asarray(jax.device_get(
        _scatter_fn(donate=False)(used.copy(), idx, delta)))

    from jax.sharding import NamedSharding, PartitionSpec as P
    fn = make_state_scatter_sharded(mesh, donate=False)
    used_sh = jax.device_put(used.copy(),
                             NamedSharding(mesh, P("nodes", None)))
    rep = NamedSharding(mesh, P())
    sharded = np.asarray(jax.device_get(
        fn(used_sh, jax.device_put(idx, rep), jax.device_put(delta, rep))))
    assert np.array_equal(single, sharded)


def test_node_slot_registry_stability_and_reuse():
    store = StateStore()
    nodes = [mock.node() for _ in range(4)]
    for n in nodes:
        store.upsert_node(n)
    reg = NodeSlotRegistry()
    ids = [n.id for n in nodes]
    first = reg.assign(ids, store=store)
    assert sorted(first.values()) == [0, 1, 2, 3]
    # stable across re-assignment and reordering
    assert reg.assign(list(reversed(ids)), store=store) == first
    # a deleted node's slot is recycled to the next joiner, lowest first
    store.delete_node(ids[1])
    joiner = mock.node()
    store.upsert_node(joiner)
    after = reg.assign([ids[0], ids[2], ids[3], joiner.id], store=store)
    assert after[joiner.id] == first[ids[1]]
    assert after[ids[0]] == first[ids[0]]
    assert reg.stats()["high_water"] == 4           # no slot-space growth


def test_parity_digest_catches_seeded_divergence(tracked):
    store, _, tracker, feed = tracked
    nodes, static = _static_over(store, 3)
    store.upsert_allocs([_alloc("ip0", nodes[0].id, 100, 64)])
    assert feed.base_for(static) is not None
    feed._epoch.base[0, 0] += 1.0                   # the seeded corruption
    assert not feed.force_verify()
    assert [v.kind for v in tracker.violations] == ["state-divergence"]
    assert feed._epoch is None                      # repair: forced resync
    base = feed.base_for(static)                    # ...and it recovers
    assert np.array_equal(base, _truth(store, static))
    with pytest.raises(AssertionError, match="nomadstate violations"):
        tracker.check()
    assert "state-divergence" in tracker.report()
