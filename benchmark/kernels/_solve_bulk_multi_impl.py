"""Bytes one launch of `_solve_bulk_multi_impl` (the count solve, up to
G_PAD evaluations a launch) has to move: every input and every output
once. The floor under any implementation. Widths are read from the
program, so a change of G_PAD or of the resource dims is followed."""

F32, I32, I16, U32, BOOL = 4, 4, 2, 4, 1


def launch_bytes(run: dict) -> float:
    from nomad_tpu.structs.resources import RESOURCE_DIMS as d
    from nomad_tpu.tensor.cluster import _pad_pow2
    from nomad_tpu.tensor.solver import BulkSolverService

    n = _pad_pow2(int(run["nodes"]))
    g, c = BulkSolverService.G_PAD, BulkSolverService.CORRECTIONS
    ins = (n * d * F32           # used0 carry
           + n * d * F32         # available
           + g * n * BOOL        # feasibility masks
           + g * n * F32         # affinity boosts
           + g * d * F32 + g * I32 + g * F32 + g * U32   # ask, k, tg, seed
           + c * I32 + c * d * F32)                      # correction slots
    outs = n * d * F32 + g * n * I16
    return float(ins + outs)
