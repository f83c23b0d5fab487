"""Solver service engages the sharded bulk engine on a multi-device
mesh (round 5: the carry itself shards; tensor/sharding.py
make_solve_bulk_multi_sharded)."""

import pytest

from nomad_tpu import mock
from nomad_tpu.structs import enums
from nomad_tpu.structs.operator import SchedulerConfiguration
from nomad_tpu.testing import Harness
from nomad_tpu.tensor.solver import get_service

def test_sharded_service_engages():
    h = Harness()
    mock.build_nodes(h.store, 512)
    cfg = SchedulerConfiguration(scheduler_algorithm=enums.SCHED_ALG_TPU_BINPACK)
    jobs = [mock.service_job(1000, cpu=50, mem=32, batch=True) for _ in range(3)]
    for j in jobs:
        h.store.upsert_job(j)
        h.process(mock.eval_for(j), sched_config=cfg)
    snap = h.store.snapshot()
    placed = sum(len(snap.allocs_by_job(j.id)) for j in jobs)
    assert placed == 3000, placed
    stats = get_service().stats
    assert stats["sharded"] >= 3, stats


# -- the ladder mix this test and chip_smoke.py build their fleets from -------


def _built_in_order(n_nodes, seed):
    """The fleet mock.build_nodes makes, in the order it made it
    (mock.node numbers its nodes process-wide, in the name)."""
    from nomad_tpu.state import StateStore

    store = StateStore()
    mock.build_nodes(store, n_nodes, seed=seed)
    return sorted(store.snapshot().nodes(),
                  key=lambda n: int(n.name.rsplit("-", 1)[1]))


def _capacities(nodes):
    return [(n.resources.cpu, n.resources.memory_mb) for n in nodes]


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_ladder_mix_is_a_function_of_index_and_seed(seed):
    a, b = _built_in_order(45, seed), _built_in_order(45, seed)
    assert _capacities(a) == _capacities(b)
    assert len(set(_capacities(a))) > 1         # a mix, not one shape
    assert _capacities(a) != _capacities(_built_in_order(45, seed + 100))
    for i, n in enumerate(a):
        assert n.attributes["rack"] == f"r{i % 20}"
        assert n.attributes["zone"] == f"z{i % 4}"
        assert n.attributes["kernel.version"] == mock.KERNELS[i % 3]
        assert n.attributes["instance.type"] == mock.ITYPES[i % 2]
        assert n.resources.cpu in (8000, 16000, 32000)
        assert n.resources.memory_mb in (16384, 32768, 65536)


def test_service_job_carries_its_arguments_onto_the_job():
    from nomad_tpu.structs.constraint import Affinity, Constraint, Spread

    spread = Spread(attribute="${attr.rack}", weight=50)
    con = Constraint(ltarget="${attr.kernel.name}", rtarget="linux",
                     operand="=")
    aff = Affinity(ltarget="${attr.zone}", rtarget="z1", operand="=",
                   weight=30)
    j = mock.service_job(37, cpu=250, mem=96, spreads=[spread],
                         constraints=[con], affinities=[aff], priority=70)
    tg = j.task_groups[0]
    assert j.type == enums.JOB_TYPE_SERVICE and j.priority == 70
    assert tg.count == 37
    assert (tg.tasks[0].resources.cpu, tg.tasks[0].resources.memory_mb) \
        == (250, 96)
    assert tg.spreads == [spread] and tg.affinities == [aff]
    assert tg.constraints == [con]
    plain = mock.service_job(3, batch=True)
    assert plain.type == enums.JOB_TYPE_BATCH and plain.priority == 50
    assert plain.task_groups[0].count == 3
    assert (plain.task_groups[0].tasks[0].resources.cpu,
            plain.task_groups[0].tasks[0].resources.memory_mb) == (100, 64)
