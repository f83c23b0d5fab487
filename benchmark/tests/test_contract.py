"""BENCHMARK.json against the contract, and each cell at --toy size
printing a last line with exactly the contract's keys."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.generators.backlog import rounds_of
from benchmark.harness import load_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_names_units_and_bounds():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = []
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        names.append(m["name"])
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        # reported only where the metric it moves is
        assert set(m.get("workloads", CELLS)) <= set(
            e2e[m["moves"]].get("workloads", CELLS)), m
        assert (ROOT / "benchmark/layer_metrics"
                / f"{m['name']}.json").exists(), m["name"]
        layers.add(m["layer"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert any(w["name"] in m.get("workloads", CELLS)
                   for m in BENCH["per_layer"])
        assert sum(w["name"] in m.get("workloads", CELLS)
                   for m in BENCH["end_to_end"]) >= 2
    assert {w["config"] for w in BENCH["workloads"]} == {
        c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert len(c["source"]) <= 200 and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
    assert (ROOT / "BENCHMARK.json").stat().st_size < 64 * 1024


def run_cell(cell: str, trace: int, seconds: int = 8, root: Path = ROOT):
    """One toy run of `cell` from the checkout at `root` -> (the result
    line, standard output, standard error)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3", "--seconds", str(seconds), "--trace", str(trace), "--toy"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    return line, proc.stdout, proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_toy_cell_prints_the_contracts_line(cell):
    line, out, err = run_cell(cell, trace=0)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "checked"}
    assert list(line)[-1] == "checked"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", CELLS)}
    assert set(line["metrics"]) == want
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in line["metrics"].values())
    assert line["correct"] is True and line["failed"] == 0, line
    # the backlog as often as the configuration asks for it (rounds)
    _, _, config, traffic = load_cell(cell, True)
    assert line["attempted"] == traffic["jobs"] * rounds_of(config, True)
    assert f"made={rounds_of(config, True)} " in out
    # each number compared beside its limit: last in the line, and the
    # last lines of standard error
    assert line["checked"]["fitness_under_reference"][1] > 0
    assert all(got <= limit for got, limit in line["checked"].values())
    last = err.strip().splitlines()[-len(line["checked"]):]
    assert [l.split()[1] for l in last] == list(line["checked"])
    assert all(l.startswith("checked ") and "(limit " in l for l in last)


@pytest.mark.parametrize("cell", CELLS)
def test_toy_traced_cell_reports_layers_and_breakdown(cell):
    line, out, _ = run_cell(cell, trace=1)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checked"}
    assert list(line)[-1] == "checked"
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    allowed = {m["name"] for m in BENCH["per_layer"]
               if cell in m.get("workloads", CELLS)}
    assert line["metrics"] and set(line["metrics"]) <= allowed
    assert 0 <= line["metrics"]["device.idle_pct"]["value"] <= 100
    # the cell's own kernel is found by the name its metric file gives
    assert line["metrics"]["solve_task_group_fused_ms"]["value"] > 0
    assert "[timeline]" in out
    assert line["breakdown"]["device_ops"]
    assert len(line["breakdown"]["device_ops"]) <= 10
    gaps = line["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    # cut along the launching thread: the labels' seconds are the idle
    # time, and more than one thing stood between the device and its
    # next launch
    assert sum(s for _, s in gaps) == pytest.approx(
        line["device"]["window_s"] - line["device"]["busy_s"], rel=1e-3)
    assert len({n for n, _ in gaps} - {"no_span", "other"}) >= 2


def test_off_a_tpu_there_is_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "0", "--seconds", "5", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_alone_in_a_directory_it_refuses(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "0", "--seconds", "5", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
