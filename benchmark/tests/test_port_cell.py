"""`grid.port.spread.300` on `grid-10k-port` (PR 38): the grid's spread
job with its two dynamic ports. The cell drains at --toy size, traced
and untraced, with its own four per-layer names read and none `null`;
the `[ports]` line counts what the toy traffic asked; with the
overlay's ports planted blind (the tree before PR 38: racing
evaluations take the lowest free port of their own snapshots) the run
ends on the configuration's guarantee; and `single_agent_ports`' three
checks each refuse a doctored store (tier-1 keeps a copy of those:
`tests/test_benchmark_port_cell.py`)."""

import os
import re
import subprocess
import sys
from pathlib import Path

from benchmark.tests.cells import listed, toy_files

ROOT = Path(__file__).resolve().parents[2]
CELL = "grid.port.spread.300"
OWN = ["placer.ports_ms", "placer.rows_ms",
       "placer.port_nodes_inflight_pct", "applier.port_collisions"]

# The parent's view in the change's place: nobody reads the ports the
# in-flight overlay holds. At the toy's own size (6 x 24 on 256 nodes,
# 8 workers) the racers collide and recover inside their five plan
# attempts (183 rows rejected on port collisions with 8 x 38, every job
# whole); the collapse needs the cell's job size and worker count, so
# the control runs 12 of the cell's own 300-allocation jobs with 24
# workers on 2,048 nodes (3,600 of 4,096 slots), one round. The program
# as it is drains that in half a second with no row rejected.
BLIND = """
import json, sys, time
sys.path.insert(0, {root!r})
from nomad_tpu.tensor.overlay import InflightOverlay
InflightOverlay.ports_on = lambda self, node_ids, snapshot_index: {{}}
from benchmark import harness
load = harness.load_cell
def bigger(name, toy):
    bench, cell, config, traffic = load(name, toy)
    traffic["jobs"] = 12
    traffic["classes"][0]["count"] = {{"cycle": [300]}}
    config["toy"].update(nodes=2048, allocations_that_fit=4096,
                         agent={{"workers": 24}}, window={{"rounds": 1}})
    return bench, cell, config, traffic
harness.load_cell = bigger
sys.exit(harness.main(["--workload", {cell!r}, "--seed", "2147483781",
                       "--seconds", "15", "--trace", "0", "--toy"],
                      time.time()))
"""


def ports_lines(out: str) -> list:
    return [dict(kv.split("=") for kv in line.split()[1:])
            for line in out.splitlines() if line.startswith("[ports] ")]


def test_the_toy_rounds_drain_whole_and_the_ports_line_counts_them(toy):
    for trace in (0, 1):
        run = toy(CELL, trace)
        assert run.line["correct"] is True and run.line["failed"] == 0
        config, traffic = toy_files(run.bench, CELL)
        (cls,) = traffic["classes"]
        allocs = traffic["jobs"] * cls["count"]["cycle"][0]
        rounds = config["toy"]["window"]["rounds"]
        assert run.line["attempted"] == traffic["jobs"] * rounds
        lines = ports_lines(run.out)
        assert [l["round"] for l in lines] == [str(k) for k in range(rounds)]
        for l in lines:
            assert int(l["jobs"]) == traffic["jobs"]
            assert int(l["allocations"]) == allocs
            assert int(l["ports"]) == int(l["asked"]) == cls["ports"] * allocs
            assert int(l["nodes"]) == config["toy"]["nodes"]
            assert 0 < int(l["nodes_holding"]) <= allocs
            assert l["violations"] == "0"
        assert run.line["checked"]["ports_twice"] == [0, 0]
        assert "evals_failed=0" in run.out
    assert set(toy(CELL, 0).line["metrics"]) == {"allocs_per_s", "setup_s"}


def test_the_four_new_names_are_read_and_none_is_null(toy):
    run = toy(CELL, 1)
    names = listed(run.bench, CELL)
    assert set(OWN) <= set(names)
    metrics = run.line["metrics"]
    # a --toy run names no device kind, so no peak and no roofline share
    # (`layers.read_kernel`); on the chip the line holds every name
    assert set(names) - set(metrics) == {"solve_task_group_fused_roofline"}
    for name in names:
        if name in metrics:
            assert isinstance(metrics[name]["value"], float), name
    assert metrics["placer.ports_ms"]["value"] > 0
    assert metrics["placer.rows_ms"]["value"] > 0
    assert 0 <= metrics["placer.port_nodes_inflight_pct"]["value"] <= 100
    assert metrics["applier.port_collisions"]["value"] == 0
    assert metrics["applier.rows_rejected"]["value"] == 0
    assert metrics["broker.out_of_attempts_pct"]["value"] == 0
    assert metrics["kernels.compiles_in_window"]["value"] == 0
    # every placement left the tier as a row and got the exact verdict
    assert metrics["placer.columnar_groups_pct"]["value"] == 0
    assert metrics["applier.columnar_nodes_pct"]["value"] == 0
    # the ports are chosen under the hold, a sibling of the registration
    spans = re.search(r"\[spans\] .*", run.out).group(0)
    assert '"placer.ports"' in spans and '"placer.rows"' in spans
    ops = dict(run.line["breakdown"]["device_ops"])
    assert "jit_solve_task_group_fused" in ops


def test_blind_to_the_overlays_ports_the_run_ends_on_the_guarantee():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", BLIND.format(root=str(ROOT), cell=CELL)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "[window] round=0" in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
    assert "share_reached=false" in proc.stdout
    # the deploy kind's line: jobs short with room, or out of attempts
    assert re.search(r"NotHeld: (attempts|placement): ", proc.stderr)
    # what was committed is sound all the same: the applier is the gate
    (line,) = ports_lines(proc.stdout)
    assert line["violations"] == "0"
    assert 0 < int(line["allocations"]) < 3600
    assert "[counters]" not in proc.stdout     # it ended in the round
