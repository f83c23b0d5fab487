"""Bytes one launch of `system_place` (a system group on every node,
tensor/kernels.py) has to move: every input and every output once. Its
node axis is the fleet padded to a power of two, its victim axis the
victim columns' padded width: build_system_victims' floor, which this
cell's nodes (six allocations each) stay under."""

F32 = 4


def launch_bytes(run: dict) -> float:
    import inspect

    from nomad_tpu.structs.resources import RESOURCE_DIMS as d
    from nomad_tpu.tensor import cluster

    build_system_victims = getattr(cluster, "build_system_victims", None)
    if build_system_victims is None:
        return None     # a program without the system placement
    _pad_pow2 = cluster._pad_pow2

    n = _pad_pow2(int(run["nodes"]))
    v = int(inspect.signature(build_system_victims)
            .parameters["v_floor"].default)
    ins = (2 * n * d * F32          # capacity, proposed usage
           + d * F32 + 1            # ask, the preemption switch
           + 2 * n                  # feasibility mask, target nodes (bool)
           + n * v * d * F32        # victims' usage
           + 3 * n * v * F32        # priority, max_parallel, plan counts
           + n * v * 4              # group column (int32)
           + n * v)                 # preemptible (bool)
    outs = n + n * v + n * F32      # placed, victims (bool), fitness
    return float(ins + outs)
