"""Multi-chip sharding tests on the virtual 8-device CPU mesh
(conftest forces xla_force_host_platform_device_count=8).

The solve's node axis shards over the mesh; each placement step does a
global argmax (XLA all-reduce). Sharded and single-device runs must
agree to the bit on choices and 1e-6 on scores.
"""

import numpy as np

import __graft_entry__ as graft
from nomad_tpu.tensor.sharding import node_mesh, shard_solve_args, solve_task_group_sharded


class TestShardedSolve:
    def test_sharded_vs_single_parity(self, eight_devices):
        args = graft._example_solve_args(n_nodes=96, k=16, s=2, v=4)
        mesh8 = node_mesh(eight_devices)
        mesh1 = node_mesh(eight_devices[:1])
        c8, f8, s8 = map(np.asarray, solve_task_group_sharded(mesh8, args))
        c1, f1, s1 = map(np.asarray, solve_task_group_sharded(mesh1, args))
        assert (c8 == c1).all()
        assert (f8 == f1).all()
        np.testing.assert_allclose(s8, s1, atol=1e-6)

    def test_sharded_no_oversubscription(self, eight_devices):
        args = graft._example_solve_args(n_nodes=64, k=32)
        mesh = node_mesh(eight_devices)
        choices, founds, _ = map(np.asarray, solve_task_group_sharded(mesh, args))
        placed = choices[founds]
        avail, used, ask = args[0], args[1], args[4]
        per_node = np.bincount(placed, minlength=avail.shape[0])
        assert ((used + per_node[:, None] * ask[None, :]) <= avail + 1e-3).all()

    def test_input_shardings_land_on_mesh(self, eight_devices):
        args = graft._example_solve_args(n_nodes=64)
        mesh = node_mesh(eight_devices)
        sharded = shard_solve_args(mesh, args)
        # the node-axis tensors really live across 8 devices
        assert len(sharded[0].sharding.device_set) == 8
        assert len(sharded[4].sharding.device_set) == 8  # replicated ask too
        shard_rows = {s.data.shape[0] for s in sharded[0].addressable_shards}
        assert shard_rows == {64 // 8}

    def test_odd_node_count_not_divisible_by_mesh(self, eight_devices):
        # 100 nodes over 8 devices: XLA pads/handles uneven sharding
        args = graft._example_solve_args(n_nodes=100, k=8)
        mesh = node_mesh(eight_devices)
        c, f, s = map(np.asarray, solve_task_group_sharded(mesh, args))
        c1, f1, s1 = map(np.asarray,
                         solve_task_group_sharded(node_mesh(eight_devices[:1]), args))
        assert (c == c1).all() and (f == f1).all()
        np.testing.assert_allclose(s, s1, atol=1e-6)


class TestDryrunArtifact:
    def test_dryrun_multichip_in_process(self):
        # conftest gives this process 8 CPU devices
        graft.dryrun_multichip(8)

    def test_dryrun_multichip_raises_without_the_devices(self):
        """A chip belongs to one process: with fewer devices than asked
        there is no child on a virtual CPU mesh to fall back to."""
        import pytest

        with pytest.raises(RuntimeError, match="needs 16 devices"):
            graft.dryrun_multichip(16)

    def test_entry_is_what_production_launches(self):
        from nomad_tpu.tensor.kernels import solve_task_group_fused

        fn, args = graft.entry()
        assert fn is solve_task_group_fused
        out = np.asarray(fn(*args))
        assert out.shape == (3, 16) and out[1].all()    # all 16 found


class TestShardedBulkEngine:
    """solve_bulk_multi_sharded: the C2M bulk engine on a mesh — one
    all-gather per eval (round 5; fixes the per-placement collective
    cadence that made the sharded rung 7.3x slower in round 4)."""

    def _bulk_inputs(self, n=256, g=4, d=4, seed=0):
        rng = np.random.RandomState(seed)
        f = np.float32
        avail = np.stack([
            rng.choice([2000, 4000, 8000], n),
            rng.choice([4096, 8192], n),
            np.full(n, 100 * 1024),
            np.full(n, 12001),
        ], axis=1).astype(f)
        used0 = np.zeros((n, d), f)
        used0[:, 0] = rng.randint(0, 1000, n)
        used0[:, 1] = rng.randint(0, 2048, n)
        feas = rng.rand(g, n) > 0.2
        aff = np.zeros((g, n), f)
        aff[0] = np.where(rng.rand(n) > 0.7, 0.5, 0.0)
        ask = np.tile(np.array([500.0, 256.0, 0.0, 0.0], f), (g, 1))
        k = np.full(g, 64, np.int32)
        seeds = np.arange(g).astype(np.uint32)
        C = 8
        cidx = np.zeros(C, np.int32)
        cdelta = np.zeros((C, d), f)
        return avail, used0, feas, aff, ask, k, seeds, cidx, cdelta

    def test_parity_with_single_device_kernel(self, eight_devices):
        import jax
        from nomad_tpu.tensor.kernels import solve_bulk_multi
        from nomad_tpu.tensor.sharding import (make_solve_bulk_multi_sharded,
                                               node_mesh, shard_bulk_state)

        avail, used0, feas, aff, ask, k, seeds, cidx, cdelta = \
            self._bulk_inputs()
        g = len(k)
        # single-device reference
        u1, c1 = solve_bulk_multi(
            jax.device_put(used0), jax.device_put(avail), feas, aff, ask,
            k, np.ones(g, np.float32), seeds, cidx, cdelta, g=g)
        u1, c1 = np.asarray(u1), np.asarray(c1)
        # sharded
        mesh = node_mesh(eight_devices)
        used_sh, avail_sh = shard_bulk_state(mesh, used0, avail)
        solve = make_solve_bulk_multi_sharded(mesh)
        u8, c8, _ = solve(used_sh, avail_sh, feas, aff, ask, k, seeds,
                          cidx, cdelta, g=g)
        u8, c8 = np.asarray(u8), np.asarray(c8)
        assert (c8 == c1).all()
        np.testing.assert_allclose(u8, u1, atol=1e-3)

    def test_no_oversubscription_and_budget(self, eight_devices):
        from nomad_tpu.tensor.sharding import (make_solve_bulk_multi_sharded,
                                               node_mesh, shard_bulk_state)

        avail, used0, feas, aff, ask, k, seeds, cidx, cdelta = \
            self._bulk_inputs(seed=3)
        g = len(k)
        mesh = node_mesh(eight_devices)
        used_sh, avail_sh = shard_bulk_state(mesh, used0, avail)
        solve = make_solve_bulk_multi_sharded(mesh)
        u8, c8, _ = solve(used_sh, avail_sh, feas, aff, ask, k, seeds,
                          cidx, cdelta, g=g)
        u8, c8 = np.asarray(u8), np.asarray(c8)
        assert (u8 <= avail + 1e-3).all()
        total = used0.copy()
        for gi in range(g):
            assert c8[gi].sum() <= k[gi]
            assert (c8[gi][~feas[gi]] == 0).all()
            total += c8[gi][:, None] * ask[gi][None, :]
        np.testing.assert_allclose(total, u8, atol=1e-3)

    def test_corrections_fold_into_sharded_carry(self, eight_devices):
        from nomad_tpu.tensor.sharding import (make_solve_bulk_multi_sharded,
                                               node_mesh, shard_bulk_state)

        avail, used0, feas, aff, ask, k, seeds, cidx, cdelta = \
            self._bulk_inputs(seed=5)
        # negative correction on a row in the LAST shard (global row 250)
        used0[250] = [1000.0, 1024.0, 0.0, 0.0]
        cidx[0] = 250
        cdelta[0] = [-1000.0, -1024.0, 0.0, 0.0]
        g = len(k)
        mesh = node_mesh(eight_devices)
        used_sh, avail_sh = shard_bulk_state(mesh, used0, avail)
        solve = make_solve_bulk_multi_sharded(mesh)
        u8, c8, _ = solve(used_sh, avail_sh, feas, aff, np.zeros_like(ask),
                          np.zeros_like(k), seeds, cidx, cdelta, g=g)
        u8 = np.asarray(u8)
        np.testing.assert_allclose(u8[250], 0.0, atol=1e-3)

    def test_parity_multi_round_fill(self, eight_devices):
        """Tiny per-node capacity forces many distributed top-k rounds
        (each node takes ~1); counts must still match single-device."""
        import jax
        from nomad_tpu.tensor.kernels import solve_bulk_multi
        from nomad_tpu.tensor.sharding import (make_solve_bulk_multi_sharded,
                                               node_mesh, shard_bulk_state)

        rng = np.random.RandomState(11)
        n, d, g = 512, 4, 2
        f = np.float32
        avail = np.zeros((n, d), f)
        avail[:, 0] = rng.choice([600, 700], n)   # fits 1 x 500 ask
        avail[:, 1] = 4096
        used0 = np.zeros((n, d), f)
        feas = rng.rand(g, n) > 0.1
        aff = np.zeros((g, n), f)
        ask = np.tile(np.array([500.0, 16.0, 0.0, 0.0], f), (g, 1))
        k = np.full(g, 200, np.int32)             # ~200 nodes @ 1 each
        seeds = np.arange(g).astype(np.uint32)
        cidx = np.zeros(8, np.int32)
        cdelta = np.zeros((8, d), f)
        u1, c1 = solve_bulk_multi(
            jax.device_put(used0), jax.device_put(avail), feas, aff, ask,
            k, np.ones(g, f), seeds, cidx, cdelta, g=g)
        mesh = node_mesh(eight_devices)
        us, av = shard_bulk_state(mesh, used0, avail)
        # small pools force the round loop to iterate
        solve = make_solve_bulk_multi_sharded(mesh, top_r=8)
        u8, c8, r8 = solve(us, av, feas, aff, ask, k, seeds, cidx, cdelta,
                           g=g)
        assert (np.asarray(c8) == np.asarray(c1)).all()
        np.testing.assert_allclose(np.asarray(u8), np.asarray(u1), atol=1e-3)
        assert np.asarray(c8)[0].sum() == 200
        # 200 placements through top_r=8 pools takes many gather rounds;
        # the reported per-eval round count is what the service bills as
        # all-gathers-per-eval
        assert int(np.asarray(r8)[0]) > 3
