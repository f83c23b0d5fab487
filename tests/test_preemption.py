"""Preemption semantics (reference scheduler/preemption.go):
priority-delta filter, migrate max_parallel penalty, network (reserved
port) and device preemption. The device kernel's parity with its host
mirror is pinned in test_preempt_solve.py."""

from nomad_tpu import mock
from nomad_tpu.scheduler.preemption import (
    MAX_PARALLEL_PENALTY,
    PRIORITY_DELTA,
    is_preemptible,
    preempt_for_device,
    preempt_for_network,
    preempt_for_task_group,
)
from nomad_tpu.structs import Job, enums
from nomad_tpu.structs.alloc import AllocatedPort
from nomad_tpu.structs.job import MigrateStrategy
from nomad_tpu.structs.resources import (
    NetworkResource,
    NodeDeviceResource,
    RequestedDevice,
    Resources,
)


def _alloc_on(node, job, cpu=1000, mem=1000, index=0):
    a = mock.alloc(job, node, index=index)
    a.allocated_vec = Resources(cpu=cpu, memory_mb=mem).vec()
    return a


class TestPriorityDelta:
    def test_within_delta_not_preemptible(self):
        """reference preemption.go filterAndGroupPreemptibleAllocs: allocs
        within 10 priority points of the asker are off-limits."""
        node = mock.node()
        j45 = mock.job(priority=45)
        a = _alloc_on(node, j45)
        assert not is_preemptible(a, 50)
        assert is_preemptible(a, 45 + PRIORITY_DELTA)

    def test_task_group_selection_skips_close_priority(self):
        node = mock.node()  # 4000 cpu / 8192 mem
        close = mock.job(priority=45)
        low = mock.job(priority=10)
        a_close = _alloc_on(node, close, cpu=2000, mem=4000, index=0)
        a_low = _alloc_on(node, low, cpu=2000, mem=4000, index=1)
        ask = Resources(cpu=1000, memory_mb=1000).vec()
        victims = preempt_for_task_group(node, [a_close, a_low], ask, 50)
        assert victims is not None
        assert [v.id for v in victims] == [a_low.id]


class TestMaxParallelPenalty:
    def test_penalty_steers_to_other_group(self):
        """A tg already at its migrate max_parallel in this plan takes a
        +50 penalty per excess eviction (reference scoreForTaskGroup), so
        an otherwise-worse-matching victim from another group wins."""
        node = mock.node()
        j1 = mock.job(priority=10)
        j2 = mock.job(priority=10)
        j2.task_groups[0].migrate = MigrateStrategy(max_parallel=1)
        a1 = _alloc_on(node, j1, cpu=2000, mem=4000, index=0)
        a2 = _alloc_on(node, j2, cpu=1900, mem=4192, index=0)
        ask = Resources(cpu=1900, memory_mb=4000).vec()
        # without prior evictions the closer resource match (a2) wins
        v = preempt_for_task_group(node, [a1, a2], ask, 80)
        assert v and v[0].id == a2.id
        # with j2's tg already at max_parallel, the penalty flips the pick
        counts = {(a2.namespace, a2.job_id, a2.task_group): 1}
        v = preempt_for_task_group(node, [a1, a2], ask, 80,
                                   preempted_counts=counts)
        assert v and v[0].id == a1.id
        assert MAX_PARALLEL_PENALTY == 50.0


class TestNetworkPreemption:
    def test_frees_conflicting_reserved_port(self):
        node = mock.node()
        low = mock.job(priority=10)
        holder = _alloc_on(node, low, cpu=100, mem=100)
        holder.allocated_ports = [AllocatedPort(label="http", value=8080)]
        bystander = _alloc_on(node, low, cpu=100, mem=100, index=1)
        ask = Resources(cpu=100, memory_mb=100,
                        networks=[NetworkResource(
                            reserved_ports=[("http", 8080)])])
        victims = preempt_for_network(node, [holder, bystander], ask, 50)
        assert victims is not None
        assert [v.id for v in victims] == [holder.id]

    def test_no_conflict_no_victims(self):
        node = mock.node()
        low = mock.job(priority=10)
        holder = _alloc_on(node, low)
        holder.allocated_ports = [AllocatedPort(label="http", value=9000)]
        ask = Resources(networks=[NetworkResource(
            reserved_ports=[("http", 8080)])])
        assert preempt_for_network(node, [holder], ask, 50) is None


class TestDevicePreemption:
    def _gpu_node(self, n_inst=2):
        node = mock.node()
        node.resources.devices = [NodeDeviceResource(
            vendor="nvidia", type="gpu", name="v100",
            instance_ids=[f"uuid{i}" for i in range(n_inst)])]
        return node

    def test_frees_largest_holder_lowest_priority(self):
        node = self._gpu_node(2)
        low = mock.job(priority=10)
        mid = mock.job(priority=30)
        a_low = _alloc_on(node, low)
        a_low.allocated_devices = {"nvidia/gpu/v100": ["uuid0"]}
        a_mid = _alloc_on(node, mid, index=1)
        a_mid.allocated_devices = {"nvidia/gpu/v100": ["uuid1"]}
        ask = [RequestedDevice(name="nvidia/gpu", count=1)]
        victims = preempt_for_device(node, [a_low, a_mid], ask, 80)
        assert victims is not None
        assert [v.id for v in victims] == [a_low.id]

    def test_insufficient_instances_returns_none(self):
        node = self._gpu_node(1)
        high = mock.job(priority=70)
        a = _alloc_on(node, high)
        a.allocated_devices = {"nvidia/gpu/v100": ["uuid0"]}
        ask = [RequestedDevice(name="nvidia/gpu", count=1)]
        # holder is within the priority delta of 75 -> not preemptible
        assert preempt_for_device(node, [a], ask, 75) is None
