#!/usr/bin/env python3
"""One traced launch shape of `solve_task_group_fused`, split by the HLO
ops of its placement loop (PERF.md section 5).

    python3 scripts/scan_profile.py --k 1200                  # this tree
    python3 scripts/scan_profile.py --k 1200 --repo .chip_tree/parent

Builds the arguments of one grid evaluation by hand (10,000 uniform
nodes padded to 16,384, 25 racks, the 6000 MHz / 6000 MB ask, an even
spread, K padded to a power of two with `k` rows active), launches the
program a few times under the profiler, and prints one `[scan_profile]`
JSON line:

- device time a launch (median of the program's `XLA Modules` events);
- the steps a launch ran (the events of a body op over the launches) and
  the launch's time over them;
- the loop body's ops: the instructions of the compiled while body with
  their layouts, and the device time of each a step, from the `XLA Ops`
  events of the same name;
- the same time totalled by the named scope (`feasibility`, `score`,
  `spread`, `select`, `usage_update`) of each op's root instruction;
- the layouts of the loop's carry (the body's parameter tuple).

`--repo` imports nomad_tpu from another checkout (the parent commit
unpacked into a directory .gitignore lists), so that one call on the
chip measures both sides. Needs the chip: a CPU run has no device
plane, and the script then prints the compiled body without times.
Nothing here is read by the benchmark; the numbers go to PERF.md by hand.
"""

from __future__ import annotations

import argparse
import glob
import json
import re
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SCOPES = ("feasibility", "score", "spread", "select", "usage_update")
PROGRAM = "jit_solve_task_group_fused"
# instructions that are no work on the device
FREE = {"get-tuple-element", "constant", "bitcast", "tuple", "parameter"}


def grid_args(n_nodes: int, n_pad: int, k: int, k_pad: int, racks: int,
              v_pad: int, seed: int):
    """(usage, *pack_solve_args(...)) of one evaluation of the grid."""
    import numpy as np

    from nomad_tpu.tensor.kernels import pack_solve_args

    rng = np.random.RandomState(seed)
    f = np.float32
    available = np.zeros((n_pad, 4), f)
    available[:n_nodes] = [14000.0, 32000.0, 100 * 1024.0, 12001.0]
    used = np.zeros((n_pad, 4), f)
    # racing evaluations have filled a third of the fleet's first slot
    used[rng.rand(n_pad) < 0.33, :2] = 6000.0
    used[n_nodes:] = 0.0
    feasible = np.arange(n_pad) < n_nodes
    active = np.arange(k_pad) < k
    val_id = np.zeros((1, n_pad), np.int32)
    val_id[0, :n_nodes] = np.arange(n_nodes) % racks
    packed = pack_solve_args(
        available, np.zeros(n_pad, np.int32), np.zeros(n_pad, np.int32),
        np.array([6000.0, 6000.0, 300.0, 0.0], f), feasible,
        np.zeros(n_pad, f), np.full(k_pad, -1, np.int32), active,
        val_id, feasible[None, :], np.zeros((1, v_pad), np.int32),
        np.full((1, v_pad), np.nan, f), np.zeros(1, bool), np.ones(1, f),
        -1.0, float(k), False, False, False,
        tie_perm=rng.permutation(n_pad).astype(np.int32))
    return (used,) + packed


def loop_body(hlo: str):
    """-> (carry layouts, [(instruction, opcode, shape, scope)]) of the
    compiled program's while body."""
    comps, cur = {}, None
    for line in hlo.split("\n"):
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = [line]
        elif line.startswith("}"):
            cur = None
        elif cur:
            comps[cur].append(line)
    body = None
    for lines in comps.values():
        for line in lines:
            m = re.search(r"while\(.*body=%([\w.\-]+)", line)
            if m:
                body = m.group(1)
    if body is None:
        return [], []
    ops = []
    carry = []
    for line in comps[body][1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(",
                     line)
        if not m:
            continue
        name, shape, op = m.groups()
        if op == "parameter":
            carry = re.findall(r"[a-z0-9]+\[[\d,]*\]\{[^}]*\}", shape)
        if op in FREE:
            continue
        path = re.search(r'op_name="([^"]*)"', line)
        parts = path.group(1).split("/") if path else []
        scope = next((s for s in SCOPES if s in parts), "")
        ops.append((name, op, shape, scope))
    return carry, ops


def device_events(trace_dir: str):
    """-> ({op name: [seconds]}, [seconds a launch of PROGRAM])."""
    import jax

    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if not paths:
        return {}, []
    data = jax.profiler.ProfileData.from_file(paths[0])
    ops, launches = {}, []
    for plane in data.planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        for line in plane.lines:
            for e in line.events:
                if line.name == "XLA Ops":
                    ops.setdefault(e.name.split(" ")[0].lstrip("%"),
                                   []).append(e.duration_ns * 1e-9)
                elif (line.name == "XLA Modules"
                      and e.name.split("(")[0] == PROGRAM):
                    launches.append(e.duration_ns * 1e-9)
    return ops, launches


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=str(ROOT))
    ap.add_argument("--nodes", type=int, default=10000)
    ap.add_argument("--k", type=int, default=1200)
    ap.add_argument("--racks", type=int, default=25)
    ap.add_argument("--launches", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ops", type=int, default=80,
                    help="body ops to print, by time")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(args.repo).resolve()))

    import jax
    import numpy as np

    from nomad_tpu.tensor.cluster import _pad_pow2
    from nomad_tpu.tensor.kernels import solve_task_group_fused

    n_pad = _pad_pow2(args.nodes)
    k_pad = _pad_pow2(args.k, floor=1)
    host = grid_args(args.nodes, n_pad, args.k, k_pad, args.racks,
                     _pad_pow2(args.racks, floor=1), args.seed)
    dev = jax.device_put(host)
    compiled = solve_task_group_fused.lower(*dev).compile()
    carry, body = loop_body(compiled.as_text())
    out = np.asarray(compiled(*dev))           # warm
    placed = int((out[1] > 0.5).sum())

    trace_dir = tempfile.mkdtemp(prefix="scan_profile.")
    jax.profiler.start_trace(trace_dir)
    for _ in range(args.launches):
        jax.block_until_ready(compiled(*dev))
    jax.profiler.stop_trace()
    ops, launches = device_events(trace_dir)

    # an op of the body runs once a step: its events over the launches
    # are the steps a launch ran, their mean its time a step
    steps = max((len(ops.get(name, [])) for name, *_ in body), default=0)
    steps_a_launch = steps / len(launches) if launches else None
    rows, by_scope = [], {}
    for name, op, shape, scope in body:
        events = ops.get(name, [])
        us = 1e6 * sum(events) / steps if steps else 0.0
        rows.append({"op": name, "opcode": op, "shape": shape[:120],
                     "scope": scope, "events": len(events), "us_a_step": us})
        by_scope[scope or "(none)"] = by_scope.get(scope or "(none)", 0.0) + us
    rows.sort(key=lambda r: -r["us_a_step"])
    print("[scan_profile] " + json.dumps({
        "repo": args.repo, "device": jax.devices()[0].device_kind,
        "shape": {"n_pad": n_pad, "d": 4, "k": args.k, "k_pad": k_pad,
                  "s": 1, "v_pad": _pad_pow2(args.racks, floor=1)},
        "placed": placed,
        "launch_ms": (1e3 * statistics.median(launches)
                      if launches else None),
        "launches": len(launches),
        "steps_a_launch": steps_a_launch,
        "launch_us_a_step": (1e6 * statistics.median(launches)
                             / steps_a_launch if steps_a_launch else None),
        "body_ops": len(body),
        "body_fusions": sum(1 for _, op, _, _ in body if op == "fusion"),
        "body_us_a_step": sum(r["us_a_step"] for r in rows),
        "us_a_step_by_scope": by_scope,
        "carry": carry,
        "ops": rows[:args.ops],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
