"""A run whose timed path is broken underneath comes out `correct:
false`: the whole harness driven at --toy size (which skips only the
look for a chip), with a fault planted in the program where it produces
its answers. `test_check.py` doctors the end state the checker is
handed; `test_replicated_cell.py` cuts an acknowledged entry from a
follower's log; here the program itself loses what it acknowledged."""

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark.tests.kept_cells import write_control
from benchmark.tests.test_contract import run_cell

ROOT = Path(__file__).resolve().parents[2]

# The store's commit of a plan drops the plan's last allocation: the
# worker was told the plan is applied, its evaluation completes, and the
# client sees every job complete with a row missing.
LOSSY_COMMIT = """
import sys, time
sys.path.insert(0, {root!r})
from nomad_tpu.state.store import StateStore
commit = StateStore.upsert_plan_results_batch

def lossy(self, payloads, ts=None):
    for p in payloads:
        rows = list(p.get("result_allocs", ()))
        if len(rows) > 1:
            p["result_allocs"] = rows[:-1]
    return commit(self, payloads, ts=ts)

StateStore.upsert_plan_results_batch = lossy
from benchmark.harness import main
sys.exit(main(["--workload", "grid.spread.300", "--seed", "2147483777",
               "--seconds", "8", "--trace", "0", "--toy"], time.time()))
"""


def test_a_commit_that_loses_an_acknowledged_row_fails_the_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", LOSSY_COMMIT.format(root=str(ROOT))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    # every job the client saw complete is a row short
    off, limit = line["checked"]["jobs_off_count"]
    assert off == line["attempted"] == line["failed"] and limit == 0
    assert f"checked jobs_off_count = {off} (limit 0)" in proc.stderr
    # and nothing else is past its limit: the fault is named, not smeared
    past = [n for n, (got, lim) in line["checked"].items() if got > lim]
    assert past == ["jobs_off_count"]


def test_the_control_of_the_fitness_comparison_is_refused(tmp_path):
    """The program's own worst-fit arm in the configured one's place
    (`kept_cells.write_control`): every count holds, and the fitness
    reads so far under the reference that the cell's own limit, the
    full-size one, refuses it many times over. (The --toy limit is
    wider than the control's reading: six racing jobs on 256 nodes.)"""
    write_control(tmp_path)
    line, out, _ = run_cell("grid.spread.300", trace=0, root=tmp_path)
    assert "algorithm=spread" in out
    traffic = json.loads(
        (ROOT / "benchmark/traffic/spread.300.json").read_text())
    limit = traffic["check"]["fitness_rel_tol"]
    got, _ = line["checked"]["fitness_under_reference"]
    assert got > 5 * limit, (got, limit)
    others = {n: v for n, v in line["checked"].items()
              if n != "fitness_under_reference"}
    assert all(value <= lim for value, lim in others.values()), others
