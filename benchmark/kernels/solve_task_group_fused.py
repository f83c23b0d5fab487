"""Bytes one launch of `solve_task_group_fused` (the per-placement scan
of one task group) has to move: every input and every output once. A
scan that re-reads its node state every step moves far more, which is
what a small share of this floor then says. The scan length is the
padded count of the evaluation, so the mean is taken over the jobs of
the run that go down the scan (counts above the host cut-over)."""

F32 = 4


def launch_bytes(run: dict) -> float:
    from nomad_tpu.structs.resources import RESOURCE_DIMS as d
    from nomad_tpu.tensor.cluster import _pad_pow2
    from nomad_tpu.tensor.placer import TPUPlacer

    n = _pad_pow2(int(run["nodes"]))
    jobs = [j for j in run["specs"] if j["count"] > TPUPlacer.HOST_CUTOVER]
    if not jobs:
        return None
    total = 0.0
    for j in jobs:
        k = _pad_pow2(int(j["count"]), floor=1)
        s = 1 if j.get("spread") else 0
        v = _pad_pow2(int(run["spread_values"]), floor=1) if s else 0
        ins = (n * (2 * d + 6) * F32      # node_mat
               + k * 2 * F32              # step_mat
               + 2 * s * n * F32          # spread_node (value ids, ok)
               + 2 * s * v * F32          # spread_tab (counts, desired)
               + s * 2 * F32 + (5 + d) * F32)
        total += ins + 3 * k * F32        # choices, founds, scores
    return total / len(jobs)
