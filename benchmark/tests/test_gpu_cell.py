"""`grid.gpu.spread.300` on `grid-10k-gpu`: the grid's spread job asking
one NVIDIA GPU through the documented device block, on a fleet of which
half carry upstream's mock.NvidiaNode() devices. The cell drains at
--toy size, traced and untraced, with its own four per-layer names read
and none `null`; the `[gpu]` line counts what the toy traffic asked;
and with the tree before the device work planted (instances chosen
without the overlay's, the applier counting instances instead of ids)
the run ends on the configuration's guarantee line. Tier-1 keeps the
reference's own cases: `tests/test_gpu_devices.py`.

At the cell's own size, on the chip, one control a run:
`python3 -m benchmark.tests.test_gpu_cell parent|worst_fit SEED` (the
parent's instances, which the guarantee line ends; WorstFit in
BestFit's place, which `correct`'s fitness line ends)."""

import os
import re
import subprocess
import sys
from pathlib import Path

from benchmark.tests.cells import listed, toy_files

ROOT = Path(__file__).resolve().parents[2]
CELL = "grid.gpu.spread.300"
OWN = ["placer.devices_ms", "build.device_columns_ms",
       "placer.device_nodes_inflight_pct", "applier.device_collisions"]

# The parent's view in the change's place: nobody reads the instances
# the in-flight overlay holds, and the applier's device verdict counts
# instances a group (two allocations on one instance pass on a node of
# two). Racing evaluations then commit one GPU twice; the collapse
# needs the cell's job size and worker count, so the by-hand control
# runs 12 of the cell's 300-allocation jobs with 24 workers on 4,096
# nodes (3,600 of the 4,096 GPU slots), one round; `main` runs it at the
# cell's own size.
PARENT = """
from nomad_tpu.structs import funcs
from nomad_tpu.tensor.overlay import InflightOverlay
InflightOverlay.devices_on = lambda self, *a, **kw: {}
InflightOverlay.devices_held = lambda self, *a, **kw: []
fit = funcs.allocs_fit
def by_count(node, allocs, check_devices=False):
    allocs = list(allocs)
    ok, dim, used = fit(node, allocs, check_devices=False)
    if ok and check_devices:
        held = {}
        for a in allocs:
            if a.should_count_for_usage():
                for gid, ids in a.allocated_devices.items():
                    held[gid] = held.get(gid, 0) + len(ids)
        cap = {g.id: len(g.instance_ids) for g in node.resources.devices}
        if any(n > cap.get(gid, 0) for gid, n in held.items()):
            return False, "device oversubscribed", used
    return ok, dim, used
funcs.allocs_fit = by_count
from nomad_tpu.core import plan_apply
plan_apply.allocs_fit = by_count
plan_apply.foreign_devices = lambda node, allocs: False
"""

# WorstFit in BestFit's place on every scan: the cell's placements are
# sound and the guarantee lines hold, and `correct`'s fitness line
# (`fitness_rel_tol`) is what ends the run.
WORST_FIT = """
import dataclasses
from nomad_tpu.tensor import placer
build = placer.build_task_group_tensors
placer.build_task_group_tensors = lambda *a, **kw: dataclasses.replace(
    build(*a, **kw), spread_alg=True)
"""

SMALLER = """
load = harness.load_cell
def smaller(name, toy):
    bench, cell, config, traffic = load(name, toy)
    traffic["jobs"] = 12
    traffic["classes"][0]["count"] = {"cycle": [300]}
    config["toy"].update(nodes=4096, allocations_that_fit=4096,
                         agent={"workers": 24}, window={"rounds": 1})
    return bench, cell, config, traffic
harness.load_cell = smaller
"""


def control(plant: str, seed: int, full: bool) -> str:
    """A program that runs the cell through the harness with `plant`
    laid over the program: at the cell's own size (`full`, on the chip)
    or at the by-hand size on any backend."""
    size = ("" if full else SMALLER)
    args = ["--workload", CELL, "--seed", str(seed), "--trace", "0"] + (
        [] if full else ["--seconds", "15", "--toy"])
    return (f"import sys, time\nsys.path.insert(0, {str(ROOT)!r})\n"
            + plant + "from benchmark import harness\n" + size
            + f"sys.exit(harness.main({args!r}, time.time()))\n")


def gpu_lines(out: str) -> list:
    return [dict(kv.split("=") for kv in line.split()[1:])
            for line in out.splitlines() if line.startswith("[gpu] ")]


def test_the_toy_rounds_drain_whole_and_the_gpu_line_counts_them(toy):
    for trace in (0, 1):
        run = toy(CELL, trace)
        assert run.line["correct"] is True and run.line["failed"] == 0
        config, traffic = toy_files(run.bench, CELL)
        (cls,) = traffic["classes"]
        allocs = traffic["jobs"] * cls["count"]["cycle"][0]
        rounds = config["toy"]["window"]["rounds"]
        assert run.line["attempted"] == traffic["jobs"] * rounds
        lines = gpu_lines(run.out)
        assert [l["round"] for l in lines] == [str(k) for k in range(rounds)]
        for l in lines:
            assert int(l["allocations"]) == int(l["device_allocations"])
            assert int(l["allocations"]) == allocs == int(l["instances"])
            assert int(l["nodes"]) == config["toy"]["nodes"]
            assert int(l["gpu_nodes"]) == config["toy"]["nodes"] // 2
            assert int(l["spread_jobs"]) == traffic["jobs"]
            assert l["violations"] == "0"
        assert "evals_failed=0" in run.out
    assert set(toy(CELL, 0).line["metrics"]) == {"allocs_per_s", "setup_s"}


def test_the_four_new_names_are_read_and_none_is_null(toy):
    run = toy(CELL, 1)
    names = listed(run.bench, CELL)
    assert set(OWN) <= set(names)
    assert "solve_task_group_fused_roofline" not in names
    metrics = run.line["metrics"]
    assert set(names) == set(metrics)
    for name in names:
        assert isinstance(metrics[name]["value"], float), name
    assert metrics["placer.devices_ms"]["value"] > 0
    assert metrics["build.device_columns_ms"]["value"] > 0
    assert metrics["placer.rows_ms"]["value"] > 0
    assert 0 <= metrics["placer.device_nodes_inflight_pct"]["value"] <= 100
    assert metrics["applier.device_collisions"]["value"] == 0
    assert metrics["applier.rows_rejected"]["value"] == 0
    assert metrics["kernels.compiles_in_window"]["value"] == 0
    # every placement left the tier as a row and got the exact verdict
    assert metrics["placer.columnar_groups_pct"]["value"] == 0
    assert metrics["applier.columnar_nodes_pct"]["value"] == 0
    spans = re.search(r"\[spans\] .*", run.out).group(0)
    assert '"placer.devices"' in spans and '"build.device_columns"' in spans
    ops = dict(run.line["breakdown"]["device_ops"])
    assert "jit_solve_task_group_fused" in ops


def test_with_the_parents_instances_the_run_ends_on_the_guarantee():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", control(PARENT, 2147483781, full=False)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "[window] round=0" in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
    # the reference's line: a GPU held twice on a node
    assert re.search(r"NotHeld: devices: \d+ violation", proc.stderr)
    assert " twice, on " in proc.stderr
    (line,) = gpu_lines(proc.stdout)
    assert int(line["violations"]) > 0
    assert "[counters]" not in proc.stdout     # it ended in the round


def test_an_idle_gap_under_the_instance_choice_is_labelled_by_it():
    """The launcher waits for the lock while the holder chooses its
    instances: the gap reads placer.devices, not the wait."""
    from benchmark.xplane import HostTimeline, label_gap, span_roles

    roles = span_roles()
    assert "placer.devices" not in roles["wait"]
    spans = [("placer.locked", 0.0, 1.0, "holder"),
             ("placer.devices", 0.2, 0.9, "holder"),
             ("placer.lock_wait", 0.1, 1.0, "next"),
             ("placer.ship", 1.0, 1.1, "next")]
    got = label_gap((0.2, 0.9), HostTimeline(spans, roles))
    assert set(got) == {"placer.devices"}
    assert abs(got["placer.devices"] - 0.7) < 1e-9


if __name__ == "__main__":
    # python3 -m benchmark.tests.test_gpu_cell parent|worst_fit SEED:
    # one control at the cell's own size, on the chip
    plant = {"parent": PARENT, "worst_fit": WORST_FIT}[sys.argv[1]]
    sys.exit(subprocess.run(
        [sys.executable, "-c", control(plant, int(sys.argv[2]), full=True)],
        cwd=ROOT).returncode)
