"""Host fingerprinting (reference client/fingerprint/, ~5k LoC).

Discovers node attributes and resources from the OS: kernel/arch/host
identity, CPU count and clock, memory, disk. Driver availability comes
from the driver registry's own health checks (the reference separates
fingerprinters and driver fingerprint loops; here drivers self-report).
"""

from __future__ import annotations

import os
import platform
import shutil
import socket
from typing import Dict, Optional

from ..structs.node import Node
from ..structs.resources import NodeResources
from ..utils import generate_uuid

VERSION = "0.1.0"


def _cpu_mhz() -> float:
    """Total compute in MHz across cores (reference fingerprints
    cpu.frequency x cpu.numcores into Resources.CPU)."""
    cores = os.cpu_count() or 1
    mhz = 0.0
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("cpu mhz"):
                    mhz = float(line.split(":")[1])
                    break
    except (OSError, ValueError):
        pass
    if mhz <= 0:
        mhz = 2000.0  # conservative default when the OS won't say
    return mhz * cores


def _memory_mb() -> float:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return float(line.split()[1]) / 1024.0
    except (OSError, ValueError):
        pass
    return 4096.0


def _disk_mb(path: str = "/") -> float:
    try:
        return shutil.disk_usage(path).free / (1024 * 1024)
    except OSError:
        return 10 * 1024.0


def _accelerators():
    """Fingerprint attached accelerators as schedulable device groups
    (reference client/devicemanager + the nvidia device plugin; here the
    detector is JAX, so TPU/GPU chips visible to the agent become
    device asks jobs can target with `device "google/tpu" {}`).

    Only consults JAX when it is ALREADY imported: the client agent must
    not pay a multi-second import (or grab an accelerator lease) just to
    fingerprint a CPU-only box. A backend that fails to initialise
    raises: "the chip is broken" must not read as "no accelerators"."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return []
    devices = jax.devices()
    from ..structs.resources import NodeDeviceResource

    groups: Dict[str, NodeDeviceResource] = {}
    for d in devices:
        platform_name = getattr(d, "platform", "") or "unknown"
        if platform_name == "cpu":
            continue
        kind = (getattr(d, "device_kind", "") or platform_name).lower()
        name = kind.replace(" ", "-")
        vendor = "google" if platform_name == "tpu" else platform_name
        dtype = "tpu" if platform_name == "tpu" else "gpu"
        key = f"{vendor}/{dtype}/{name}"
        grp = groups.get(key)
        if grp is None:
            grp = groups[key] = NodeDeviceResource(
                vendor=vendor, type=dtype, name=name,
                attributes={"platform": platform_name})
        grp.instance_ids.append(f"{dtype}-{d.id}")
    return list(groups.values())


def fingerprint(node_id: Optional[str] = None,
                datacenter: str = "dc1",
                node_class: str = "",
                drivers: Optional[Dict[str, bool]] = None,
                data_dir: str = "/") -> Node:
    """Build a Node from the host (reference client/fingerprint_manager.go)."""
    cores = os.cpu_count() or 1
    attrs = {
        "kernel.name": platform.system().lower(),
        "kernel.version": platform.release(),
        "os.name": platform.system().lower(),
        "arch": platform.machine(),
        "cpu.arch": platform.machine(),
        "cpu.numcores": str(cores),
        "cpu.totalcompute": str(int(_cpu_mhz())),
        "memory.totalbytes": str(int(_memory_mb() * 1024 * 1024)),
        "nomad.version": VERSION,
        "unique.hostname": socket.gethostname(),
    }
    if drivers is None:
        from .drivers import available_drivers

        drivers = {name: True for name in available_drivers()}
    for name, healthy in drivers.items():
        attrs[f"driver.{name}"] = "1" if healthy else "0"

    accelerators = _accelerators()
    for grp in accelerators:
        attrs[f"device.{grp.id}.count"] = str(len(grp.instance_ids))

    node = Node(
        id=node_id or generate_uuid(),
        name=socket.gethostname(),
        datacenter=datacenter,
        node_class=node_class,
        attributes=attrs,
        resources=NodeResources(
            cpu=_cpu_mhz(),
            memory_mb=_memory_mb(),
            disk_mb=_disk_mb(data_dir),
            total_cores=cores,
            devices=accelerators,
        ),
        drivers=dict(drivers),
    )
    node.compute_class()
    return node
