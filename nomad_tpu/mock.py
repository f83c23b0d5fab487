"""Test fixtures (reference nomad/mock: node.go:12, job.go:14, alloc.go:13, mock.go:90)."""

from __future__ import annotations

import itertools
import random

from .structs import (
    Allocation,
    Constraint,
    Evaluation,
    Job,
    Node,
    Resources,
    Task,
    TaskGroup,
    comparable,
    enums,
)
from .structs.alloc import alloc_name
from .structs.job import ReschedulePolicy, UpdateStrategy
from .structs.resources import NodeResources, NodeDeviceResource
from .utils import generate_uuid

_counter = itertools.count()


def node(**overrides) -> Node:
    """A 4-core/4GHz, 8GB, 100GB linux node (reference mock.Node)."""
    i = next(_counter)
    n = Node(
        id=generate_uuid(),
        name=f"node-{i}",
        datacenter="dc1",
        node_class="",
        attributes={
            "kernel.name": "linux",
            "arch": "x86_64",
            "cpu.arch": "amd64",
            "nomad.version": "0.1.0",
            "driver.exec": "1",
            "driver.mock": "1",
            "unique.hostname": f"node-{i}.local",
        },
        resources=NodeResources(cpu=4000, memory_mb=8192, disk_mb=100 * 1024, total_cores=4),
        drivers={"exec": True, "mock": True, "raw_exec": True},
        status=enums.NODE_STATUS_READY,
    )
    for k, v in overrides.items():
        setattr(n, k, v)
    n.compute_class()
    return n


def job(**overrides) -> Job:
    """A service job: 10x web group, 500MHz/256MB, exec driver
    (reference mock.Job)."""
    j = Job(
        id=f"job-{generate_uuid()[:8]}",
        name="my-job",
        type=enums.JOB_TYPE_SERVICE,
        priority=50,
        datacenters=["dc1"],
        constraints=[Constraint(ltarget="${attr.kernel.name}", rtarget="linux", operand="=")],
        task_groups=[
            TaskGroup(
                name="web",
                count=10,
                tasks=[
                    Task(
                        name="web",
                        driver="exec",
                        config={"command": "/bin/date"},
                        resources=Resources(cpu=500, memory_mb=256),
                    )
                ],
                reschedule_policy=ReschedulePolicy(attempts=2, interval_s=10 * 60, delay_s=5,
                                                   delay_function="constant", unlimited=False),
                update=UpdateStrategy(max_parallel=1),
            )
        ],
        status=enums.JOB_STATUS_PENDING,
    )
    j.name = j.id
    for k, v in overrides.items():
        setattr(j, k, v)
    return j


def batch_job(**overrides) -> Job:
    j = job(**overrides)
    j.type = enums.JOB_TYPE_BATCH
    for tg in j.task_groups:
        tg.update = None
    return j


def system_job(**overrides) -> Job:
    """Reference mock.SystemJob: runs on every node."""
    j = job(**overrides)
    j.type = enums.JOB_TYPE_SYSTEM
    j.priority = 100
    for tg in j.task_groups:
        tg.count = 1
        tg.update = None
        tg.reschedule_policy = None
    return j


def sysbatch_job(**overrides) -> Job:
    j = system_job(**overrides)
    j.type = enums.JOB_TYPE_SYSBATCH
    j.priority = 50
    return j


def eval_for(j: Job, **overrides) -> Evaluation:
    ev = Evaluation(
        id=generate_uuid(),
        namespace=j.namespace,
        priority=j.priority,
        type=j.type,
        job_id=j.id,
        triggered_by=enums.TRIGGER_JOB_REGISTER,
        status=enums.EVAL_STATUS_PENDING,
    )
    for k, v in overrides.items():
        setattr(ev, k, v)
    return ev


def alloc(j: Job = None, n: Node = None, index: int = 0, **overrides) -> Allocation:
    """A placed, running alloc of the mock job's web group (reference mock.Alloc)."""
    if j is None:
        j = job()
    if n is None:
        n = node()
    tg = j.task_groups[0]
    a = Allocation(
        id=generate_uuid(),
        eval_id=generate_uuid(),
        name=alloc_name(j.id, tg.name, index),
        namespace=j.namespace,
        node_id=n.id,
        node_name=n.name,
        job_id=j.id,
        job=j,
        job_version=j.version,
        task_group=tg.name,
        allocated_vec=tg.combined_resources().vec(),
        desired_status=enums.ALLOC_DESIRED_RUN,
        client_status=enums.ALLOC_CLIENT_RUNNING,
    )
    for k, v in overrides.items():
        setattr(a, k, v)
    return a


def gpu_node(**overrides) -> Node:
    n = node(**overrides)
    n.resources.devices = [
        NodeDeviceResource(
            vendor="nvidia", type="gpu", name="t4",
            instance_ids=[generate_uuid() for _ in range(4)],
            attributes={"memory_mb": 16384},
        )
    ]
    n.compute_class()
    return n


# -- the ladder mix: BASELINE.md's cluster shape, used by chip_smoke.py
#    and the sharded-solver tests ----------------------------------------

RACKS = 20
ZONES = 4
KERNELS = ["4.14.0", "4.19.0", "5.10.0"]
ITYPES = ["small", "large"]


def shape_node(n: Node, i: int, rng: random.Random) -> None:
    """The ladder's node mix, applied to node number `i`: 20 racks, 4
    zones, 3 kernels, 2 instance types, and a seeded draw of capacity."""
    n.attributes["rack"] = f"r{i % RACKS}"
    n.attributes["zone"] = f"z{i % ZONES}"
    n.attributes["kernel.version"] = KERNELS[i % len(KERNELS)]
    n.attributes["instance.type"] = ITYPES[i % len(ITYPES)]
    n.resources.cpu = rng.choice([8000, 16000, 32000])
    n.resources.memory_mb = rng.choice([16384, 32768, 65536])
    n.compute_class()


def build_nodes(store, n_nodes: int, seed: int = 0) -> None:
    """`n_nodes` ladder-mix nodes into `store`, capacities from `seed`."""
    rng = random.Random(seed)
    for i in range(n_nodes):
        n = node()
        shape_node(n, i, rng)
        store.upsert_node(n)


def service_job(count: int, cpu: int = 100, mem: int = 64, *,
                spreads=None, constraints=None, affinities=None,
                batch: bool = False, priority: int = 50) -> Job:
    j = batch_job() if batch else job()
    j.priority = priority
    tg = j.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    if spreads:
        tg.spreads = list(spreads)
    if constraints:
        tg.constraints = list(constraints)
    if affinities:
        tg.affinities = list(affinities)
    return j
