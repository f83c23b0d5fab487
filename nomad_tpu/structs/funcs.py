"""Fit & scoring math (reference nomad/structs/funcs.go:141-278).

These are the scalar/host-side versions, written against dense resource
vectors so they vectorize over nodes with numpy. The JAX device kernels in
nomad_tpu.ops.scoring reproduce exactly the same formulas; differential
tests pin them together.
"""

from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

from .resources import R_CPU, R_MEM, RESOURCE_DIMS, dim_name

# Reference scheduler/rank.go:18 binPackingMaxFitScore
BINPACK_MAX_FIT_SCORE = 18.0


def compute_free_percentage(available_vec: np.ndarray, util_vec: np.ndarray) -> Tuple[float, float]:
    """Free fraction of cpu/mem after `util` is placed
    (reference funcs.go:213 computeFreePercentage).

    available_vec = node total - node reserved.

    A zero-capacity dimension with nonzero util yields free = -inf (Go's
    float division by zero gives +Inf utilization), so that dimension's
    10^free term vanishes downstream — same end behavior as the reference.
    The 0/0 case (zero capacity, zero util) is pinned to free = 0.0 rather
    than Go's NaN so no NaN ever escapes into the kernels.
    """
    with np.errstate(divide="ignore", invalid="ignore"):
        free_cpu = 1.0 - (util_vec[R_CPU] / available_vec[R_CPU])
        free_mem = 1.0 - (util_vec[R_MEM] / available_vec[R_MEM])
    if np.isnan(free_cpu):
        free_cpu = 0.0
    if np.isnan(free_mem):
        free_mem = 0.0
    return free_cpu, free_mem


def score_fit_binpack(available_vec: np.ndarray, util_vec: np.ndarray) -> float:
    """BestFit-v3: score = 20 - (10^freeCpu + 10^freeMem), clamped [0, 18]
    (reference funcs.go:236 ScoreFitBinPack)."""
    free_cpu, free_mem = compute_free_percentage(available_vec, util_vec)
    total = 10.0 ** free_cpu + 10.0 ** free_mem
    return float(np.clip(20.0 - total, 0.0, BINPACK_MAX_FIT_SCORE))


def score_fit_spread(available_vec: np.ndarray, util_vec: np.ndarray) -> float:
    """WorstFit: score = (10^freeCpu + 10^freeMem) - 2, clamped [0, 18]
    (reference funcs.go:263 ScoreFitSpread)."""
    free_cpu, free_mem = compute_free_percentage(available_vec, util_vec)
    total = 10.0 ** free_cpu + 10.0 ** free_mem
    return float(np.clip(total - 2.0, 0.0, BINPACK_MAX_FIT_SCORE))


def allocs_fit(node, allocs: Iterable, check_devices: bool = False):
    """Do these allocs fit on the node? -> (fit, failing_dimension, used_vec)

    Mirrors reference funcs.go:141 AllocsFit: client-terminal allocs are
    free; reserved cores must not overlap; assigned ports must not
    collide (with each other or the node's agent-reserved ports); used
    must be a subset of available (total - reserved); optionally, no
    device instance of the node held twice (reference
    DeviceAccounter.AddAllocs, which skips an instance the node no longer
    has: `foreign_devices` is the applier's test of new placements for
    that). The port and instance checks are what let the serialized plan
    applier catch two concurrent plans double-booking a port or a GPU
    (reference plan_apply.go evaluateNodePlan -> AllocsFit)."""
    allocs = list(allocs)
    used = np.zeros(RESOURCE_DIMS, dtype=np.float64)
    seen_cores: set = set()
    core_overlap = False
    dev_held: set = set()
    dev_clash = False
    node_devs = node_instances(node) if check_devices else frozenset()
    any_ports = False

    for alloc in allocs:
        if not alloc.should_count_for_usage():
            continue
        used += alloc.allocated_vec
        any_ports = any_ports or bool(alloc.allocated_ports)
        for core in alloc.allocated_cores:
            if core in seen_cores:
                core_overlap = True
            seen_cores.add(core)
        if check_devices:
            for dev_id, inst in alloc.allocated_devices.items():
                for i in inst:
                    if (dev_id, i) in node_devs:
                        dev_clash = dev_clash or (dev_id, i) in dev_held
                        dev_held.add((dev_id, i))

    if core_overlap:
        return False, "cores", used

    if any_ports:
        from .network import check_port_collisions

        colliding = check_port_collisions(node, allocs)
        if colliding:
            return False, f"port collision {colliding[0]}", used

    available = node.available_vec()
    over = used > available
    if over.any():
        return False, dim_name(int(np.argmax(over))), used

    if dev_clash:
        return False, "device oversubscribed", used

    return True, "", used


def node_instances(node) -> frozenset:
    """The node's device instances, as (device group id, instance id)."""
    return frozenset((g.id, i) for g in node.resources.devices
                     for i in g.instance_ids)


def foreign_devices(node, allocs: Iterable) -> bool:
    """Does any of these allocs hold a device instance that is no
    instance of a group of the node? The applier asks it of a plan's new
    placements only: a running alloc whose instance the node has since
    dropped is skipped, as allocs_fit skips it, and does not close the
    node to later plans."""
    held = {(dev_id, i) for a in allocs
            for dev_id, inst in a.allocated_devices.items() for i in inst}
    return bool(held) and not held <= node_instances(node)


def proposed_usage(allocs: Iterable) -> np.ndarray:
    """Sum of comparable usage for non-client-terminal allocs."""
    used = np.zeros(RESOURCE_DIMS, dtype=np.float64)
    for alloc in allocs:
        if alloc.should_count_for_usage():
            used += alloc.allocated_vec
    return used
