"""`grid.system.preempt` on `grid-10k-system`: a system job
rolled out onto a full cluster. The cell runs at --toy size, traced and
untraced, its `[system]` lines counting what was read and its own five
per-layer names read; two planted faults each end the run on their
guarantee line (victims in id order instead of by distance; no
evaluation for a preempted job); and the plain reference
`benchmark/reference/preemption.py` on hand cases."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.reference import preemption as reference
from benchmark.tests.cells import listed, toy_files

ROOT = Path(__file__).resolve().parents[2]
CELL = "grid.system.preempt"
OWN = ["system.place_ms", "preempt.columns_ms", "system_place_ms",
       "system_place_roofline", "preempt.host_fallback_pct"]

RUN = """
import sys, time
sys.path.insert(0, {root!r})
{fault}
from benchmark import harness
sys.exit(harness.main(["--workload", {cell!r}, "--seed", "2147483771",
                       "--seconds", "15", "--trace", "0", "--toy"],
                      time.time()))
"""

# within a priority group the first candidate in (priority, id) order,
# whatever its size: the distance term read as zero
BY_ID = """
import jax, jax.numpy as jnp
from nomad_tpu.tensor import kernels
class _ById:
    def __getattr__(self, name):
        return getattr(jnp, name)
    @staticmethod
    def sqrt(x):
        return x * 0.0
kernels.system_place = jax.jit(
    lambda *a: kernels._system_place_xp(_ById(), kernels._fori, *a))
"""

# the applier without upstream's PreemptionEvals: a plan that preempts
# creates no evaluation
NO_EVALS = """
from nomad_tpu.core.plan_apply import PlanApplier
PlanApplier._preemption_evals = staticmethod(lambda snap, result: [])
"""


def system_lines(out: str) -> list:
    return [dict(kv.split("=", 1) for kv in re.findall(r"(\w+=\S+)", line))
            for line in out.splitlines()
            if line.startswith("[system] round=")]


def test_the_toy_rounds_preempt_a_filler_on_every_node(toy):
    for trace in (0, 1):
        run = toy(CELL, trace)
        assert run.line["correct"] is True and run.line["failed"] == 0
        config, _ = toy_files(run.bench, CELL)
        nodes = config["toy"]["nodes"]
        rounds = config["toy"]["window"]["rounds"]
        assert run.line["attempted"] == rounds
        lines = system_lines(run.out)
        assert [l["round"] for l in lines] == [str(k) for k in range(rounds)]
        for l in lines:
            for key in ("eligible", "placed", "reference_placed", "victims",
                        "reference_victims", "ports", "asked"):
                assert int(l[key]) == nodes, (key, l)
            assert l["victim_types"] == f"batch:{nodes}"
            assert int(l["jobs_preempted"]) == int(l["preemption_evals"]) > 0
            assert l["violations"] == "0"
        assert "roomy=0" in run.out and "short=0" in run.out
        assert run.line["checked"]["jobs_off_count"] == [0, 0]


def test_the_five_new_names_are_read(toy):
    run = toy(CELL, 1)
    names = listed(run.bench, CELL)
    assert set(OWN) <= set(names)
    metrics = run.line["metrics"]
    # a --toy run names no device kind: no peak, no roofline share
    assert set(names) - set(metrics) == {"system_place_roofline"}
    assert metrics["preempt.host_fallback_pct"]["value"] == 0
    assert metrics["system.place_ms"]["value"] > \
        metrics["preempt.columns_ms"]["value"] > 0
    assert metrics["kernels.compiles_in_window"]["value"] == 0
    # every node of the plan got its verdict as arrays
    applier = re.search(r"\[counters\] .*applier=(\{[^}]*\})", run.out)
    verdicts = json.loads(applier.group(1))
    assert verdicts["nodes_verified_columnar"] == \
        verdicts["nodes_verified"] > 0
    ops = dict(run.line["breakdown"]["device_ops"])
    assert "jit_system_place" in ops


@pytest.mark.parametrize("fault,line", [
    (BY_ID, r"victims \{.*'service'"),
    (NO_EVALS, r"has no evaluation triggered by preemption")])
def test_a_planted_fault_ends_the_run_on_its_guarantee(fault, line):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         RUN.format(root=str(ROOT), cell=CELL, fault=fault)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "[window] round=0" in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")
    assert re.search("NotHeld: .*" + line, proc.stderr), proc.stderr[-2000:]
    (first,) = system_lines(proc.stdout)
    assert first["violations"] != "0"


# -- the reference on hand cases ------------------------------------------

CAP = [2000, 2000, 0, 0]


def alloc(aid, prio, cpu, mem=0, mp=0, group=None):
    return {"id": aid, "priority": prio, "vec": [cpu, mem, 0, 0],
            "max_parallel": mp, "group": group or aid}


def test_reference_takes_nothing_where_the_ask_fits():
    assert reference.choose(CAP, [alloc("a", 10, 1000)],
                            [500, 0, 0, 0], 100) == []


def test_reference_cannot_free_work_within_ten_of_the_job():
    allocs = [alloc("a", 95, 1000), alloc("b", 91, 1000)]
    assert reference.choose(CAP, allocs, [500, 0, 0, 0], 100) is None
    assert reference.choose(CAP, allocs, [500, 0, 0, 0], 105) == ["b"]


def test_reference_takes_the_lowest_priority_group_first():
    allocs = [alloc("a", 40, 500), alloc("b", 20, 1500)]
    assert reference.choose(CAP, allocs, [500, 0, 0, 0], 100) == ["b"]


def test_reference_distance_decides_inside_a_group():
    allocs = [alloc("a", 50, 1500), alloc("b", 50, 500)]
    assert reference.choose(CAP, allocs, [500, 0, 0, 0], 100) == ["b"]
    # equal distance: the lower id
    allocs = [alloc("d", 50, 1000), alloc("c", 50, 1000)]
    assert reference.choose(CAP, allocs, [500, 0, 0, 0], 100) == ["c"]


def test_reference_max_parallel_penalty_moves_the_choice():
    allocs = [alloc("a", 50, 500, mp=1, group="g1"),
              alloc("b", 50, 600, group="g2"), alloc("c", 50, 900)]
    assert reference.choose(CAP, allocs, [500, 0, 0, 0], 100) == ["a"]
    counts = {"g1": 1}
    assert reference.choose(CAP, allocs, [500, 0, 0, 0], 100,
                            counts) == ["b"]
    assert counts == {"g1": 1, "g2": 1}


def test_reference_filter_superset_drops_what_is_not_needed():
    allocs = [alloc("low", 20, 1000, 0), alloc("mid", 40, 1000, 1000)]
    assert reference.choose(CAP, allocs, [1000, 1500, 0, 0],
                            100) == ["mid"]
