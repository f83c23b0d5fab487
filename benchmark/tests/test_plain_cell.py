"""`grid.plain.300` on `grid-10k-plain` (PR 36): the count solve's cell
drains round after round because the solver service's usage carry hears
of a purge, its seven own per-layer names are read, the parked service
is labelled among the idle gaps, and the parent's fault, planted, ends
the run on the configuration's liveness guarantee."""

import os
import re
import subprocess
import sys
from pathlib import Path

from benchmark.tests.cells import listed

ROOT = Path(__file__).resolve().parents[2]
CELL = "grid.plain.300"
OWN = ["solver.wait_ms", "solver.evals_per_launch", "solver.resyncs",
       "solve_bulk_multi_ms", "solve_bulk_multi_roofline",
       "solver.idle_ms", "solver.stale_frees"]

# The parent's program in the change's place: the placer hands the
# service no free epoch, so its carry never hears of the purge between
# the two toy rounds.
DEAF_CARRY = """
import sys, time
sys.path.insert(0, {root!r})
from nomad_tpu.tensor import incremental
incremental.free_epoch_fn = lambda store: None
from benchmark.harness import main
sys.exit(main(["--workload", {cell!r}, "--seed", "2147483779",
               "--seconds", "6", "--trace", "0", "--toy"], time.time()))
"""


def rounds_made(out: str) -> tuple:
    m = re.search(r"\[rounds\] asked=(\d+) made=(\d+)", out)
    return int(m.group(1)), int(m.group(2))


def test_both_toy_rounds_drain_and_the_line_is_whole(toy):
    for trace in (0, 1):
        run = toy(CELL, trace)
        assert run.line["correct"] is True and run.line["failed"] == 0
        assert rounds_made(run.out) == (2, 2)
        assert run.out.count("share_reached=true") == 2
        assert run.line["attempted"] == 12
    assert set(toy(CELL, 0).line["metrics"]) == {"allocs_per_s", "setup_s"}


def test_the_seven_new_names_are_read(toy):
    run = toy(CELL, 1)
    names = listed(run.bench, CELL)
    assert set(OWN) <= set(names) and len(names) == 22
    metrics = run.line["metrics"]
    # a --toy run names no device kind, so no peak and no roofline share
    # (`layers.read_kernel`); on the chip the line holds all 22
    absent = {"solve_bulk_multi_roofline"}
    assert set(names) - set(metrics) == absent
    for name in set(OWN) - absent:
        assert isinstance(metrics[name]["value"], float), name
    assert metrics["solver.evals_per_launch"]["value"] >= 1
    assert metrics["solve_bulk_multi_ms"]["value"] > 0
    assert metrics["solver.idle_ms"]["value"] > 0
    # the first window lands on nothing a purge freed: it owes no resync
    assert metrics["solver.stale_frees"]["value"] == 0
    ops = dict(run.line["breakdown"]["device_ops"])
    assert "jit__solve_bulk_multi_impl" in ops
    assert "jit_solve_task_group_fused" not in ops


def test_the_parked_service_is_labelled_among_the_idle_gaps(toy):
    gaps = dict(toy(CELL, 1).line["breakdown"]["idle_gaps"])
    assert gaps.get("wait.solver.idle", 0.0) > 0
    assert gaps.get("no_span", 0.0) <= gaps["wait.solver.idle"]


def test_a_carry_deaf_to_the_purge_ends_the_run_on_the_liveness_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         DEAF_CARRY.format(root=str(ROOT), cell=CELL)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    # round 0 drained, round 1 stalled to --seconds, no result line
    assert "[window] round=0" in proc.stdout
    assert "share_reached=false" in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
    assert "NotLive: liveness:" in proc.stderr
    m = re.search(
        r"(\d+) blocked evaluation\(s\) of (\d+) live job\(s\), (\d+) of "
        r"them whole, that miss (\d+) placement\(s\), with (\d+) live",
        proc.stderr)
    blocked, jobs, whole, missing, live = map(int, m.groups())
    assert blocked >= jobs >= 1 and whole < jobs and missing + live == 1800
