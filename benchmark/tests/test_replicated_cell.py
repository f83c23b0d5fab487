"""`grid.r3.spread.300` (configuration `grid-10k-r3`, deploy kind
`three_servers`) is admitted and run by the harness as it stands, its
eight `raft.*` readers read a number there and nothing on a single
server, and a data directory that lost an acknowledged plan entry makes
the run `correct: false`."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from benchmark import layers

ROOT = Path(__file__).resolve().parents[2]
CELL = "grid.r3.spread.300"
RAFT = ["raft.commit_ms", "raft.encode_ms", "raft.fsync_ms",
        "raft.replicate_ms", "raft.apply_ms", "raft.entries_per_fsync",
        "raft.bytes_per_alloc", "raft.leader_changes"]


def _run(cwd, cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483999", "--seconds", "20", "--trace", str(trace), "--toy"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout, json.loads(proc.stdout.strip().splitlines()[-1])


def test_benchmark_json_lists_the_cell_and_its_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = {c["name"]: c for c in bench["configs"]}["grid-10k-r3"]
    assert config["reduced"] == ["schedulers", "hosts"]
    on_disk = json.loads((ROOT / config["file"]).read_text())
    assert on_disk["deploy"] == "three_servers" and on_disk["servers"] == 3
    assert on_disk["reduced"] == config["reduced"]
    assert on_disk["source"] == config["source"]
    grid = json.loads((ROOT / "benchmark/configs/grid-10k.json").read_text())
    for key in ("upstream", "node_mix", "task_ask", "job_sizes",
                "allocations_that_fit", "agent", "nodes"):
        assert on_disk[key] == grid[key], key
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "grid-10k-r3", "spread.300", 1)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in RAFT:
        m = per_layer[name]
        assert m["layer"] == "raft" and m["workloads"] == [CELL]
        spec = layers.load(name)
        assert (spec["layer"], spec["unit"], spec["moves"]) == (
            "raft", m["unit"], "allocs_per_s")
    # the split of the two critical sections is read in the new cell too;
    # placer.host_locked_pct is not: its reader divides the host phases of
    # the whole window by the traced part of it (4 s of about 10 here)
    for name, m in per_layer.items():
        if name == "placer.host_locked_pct":
            assert CELL not in m["workloads"]
        elif name.startswith(("placer.", "store.")) and "workloads" in m \
                or name == "applier.rows_rejected_pct":
            assert CELL in m["workloads"], name


def test_the_cell_runs_and_every_raft_reader_reads_a_number():
    out, line = _run(ROOT, CELL, 1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 6
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        applies = "workloads" not in m or CELL in m["workloads"]
        if applies and not m["name"].endswith("_roofline"):
            # (the roofline needs a device kind, which --toy has not)
            assert m["name"] in line["metrics"], m["name"]
    for name in RAFT:
        assert isinstance(line["metrics"][name]["value"], float), name
    assert line["metrics"]["raft.leader_changes"]["value"] == 0.0
    assert line["metrics"]["raft.entries_per_fsync"]["value"] >= 1.0
    assert line["metrics"]["raft.bytes_per_alloc"]["value"] > 100
    assert line["metrics"]["kernels.compiles_in_window"]["value"] == 0.0
    replica = [ln for ln in out.splitlines() if ln.startswith("[replica]")]
    assert len(replica) == 1 and "on_disk" in replica[0]
    starts = [ln for ln in out.splitlines() if "agent started:" in ln]
    assert len(starts) == 3
    assert sum("device=none" in ln for ln in starts) == 2
    untraced = _run(ROOT, CELL, 0)[1]
    assert set(untraced["metrics"]) == {"allocs_per_s", "setup_s"}
    assert untraced["correct"] is True


def test_raft_readers_find_nothing_on_a_single_server(tmp_path):
    """The same readers listed for `grid.spread.300`, which runs one
    server with no log: none of them reads, none raises, the line leaves
    them out."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__",
                                                  "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in RAFT:
            m["workloads"].append("grid.spread.300")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    _, line = _run(tmp_path, "grid.spread.300", 1)
    assert line["correct"] is True
    assert not [n for n in line["metrics"] if n.startswith("raft.")]
    assert "placer.solve_ms" in line["metrics"]


def test_a_data_directory_that_lost_a_plan_entry_fails_the_run(
        monkeypatch, capsys):
    """Doctored state, as test_check.py doctors a store: when the
    durability check kills server-2, the last acknowledged plan entry is
    cut from its log. The check logs `replica check:` and the harness's
    watcher turns the record into `correct: false`."""
    from benchmark import harness
    from benchmark.deploy import three_servers

    kill = three_servers.Follower.kill
    cut = []

    def kill_and_cut(self):
        kill(self)
        path = self.data_dir / "raft" / "log.jsonl"
        if self.id == "server-2" and not cut and path.exists():
            lines = path.read_text().splitlines(keepends=True)
            plans = [i for i, ln in enumerate(lines)
                     if "upsert_plan_results" in ln and "Allocation" in ln]
            cut.append(json.loads(lines[plans[-1]])["index"])
            path.write_text("".join(lines[:plans[-1]]))

    monkeypatch.setattr(three_servers.Follower, "kill", kill_and_cut)
    rc = harness.main(["--workload", CELL, "--seed", "31", "--seconds", "20",
                       "--trace", "0", "--toy"], time.time())
    out = capsys.readouterr().out
    assert rc == 0 and cut
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    check = [ln for ln in out.splitlines() if ln.startswith("[check]")][-1]
    assert "replica check: data directory server-2" in check
    errors = [ln for ln in out.splitlines() if ln.startswith("[errors]")][-1]
    assert "fatal=0" not in errors
