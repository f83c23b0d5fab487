"""A cell, a configuration or a per-layer metric dropped into the
directories is found with no edit to code."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from benchmark import layers

ROOT = Path(__file__).resolve().parents[2]


C2M_METRICS = [("solver.wait_ms", "ms", "lower", "program_span"),
               ("solver.evals_per_launch", "evals", "higher",
                "program_counter"),
               ("solver.resyncs", "count", "lower", "program_counter"),
               ("solve_bulk_multi_ms", "ms", "lower", "device_trace"),
               ("solve_bulk_multi_roofline", "%", "higher", "device_trace")]


def test_a_new_cell_and_a_new_layer_metric_are_files_and_entries(tmp_path):
    """`c2m.backlog` is kept as files (configuration, traffic, readers)
    and listed in no BENCHMARK.json until its metric repeats (PERF.md
    section 4). Here a copy of the benchmark gets its entries, plus one
    new layer-metric file and entry: the cell runs (at --toy size) with
    no edit to code, and its traced line carries the metrics."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__",
                                                  "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads(
        (ROOT / "benchmark/configs/c2m-10k.json").read_text())
    bench["configs"].append({
        "name": "c2m-10k", "source": config["source"],
        "file": "benchmark/configs/c2m-10k.json",
        "reduced": config["reduced"], "why": "test"})
    bench["workloads"].append({
        "name": "c2m.backlog", "config": "c2m-10k", "traffic": "backlog",
        "chips": 1, "why": "test"})
    (tmp_path / "benchmark/layer_metrics/worker.snapshot_ms.json").write_text(
        json.dumps({"layer": "scheduler worker", "unit": "ms",
                    "moves": "allocs_per_s",
                    "reader": {"kind": "span", "span": "worker.snapshot",
                               "stat": "median", "scale": 1000}}))
    for name, unit, better, source in C2M_METRICS + [
            ("worker.snapshot_ms", "ms", "lower", "program_span")]:
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layers.load(name, tmp_path / "benchmark/layer_metrics")[
                "layer"],
            "moves": "allocs_per_s", "workloads": ["c2m.backlog"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "c2m.backlog",
         "--seed", "4", "--seconds", "8", "--trace", "1", "--toy"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 24
    for name in ("worker.snapshot_ms", "solver.wait_ms",
                 "solver.evals_per_launch", "solve_bulk_multi_ms"):
        assert line["metrics"][name]["value"] > 0, name


def test_layer_metric_readers(tmp_path):
    (tmp_path / "applier.plans.json").write_text(json.dumps({
        "layer": "plan applier", "unit": "count", "moves": "allocs_per_s",
        "reader": {"kind": "counter", "num": ["applier.applied"]}}))
    (tmp_path / "custom.json").write_text(json.dumps({
        "layer": "x", "unit": "count", "moves": "allocs_per_s"}))
    (tmp_path / "custom.py").write_text(
        "def read(obs):\n    return len(obs['spans']['durations'])\n")
    (tmp_path / "nothing.json").write_text(json.dumps({
        "layer": "x", "unit": "ms", "moves": "allocs_per_s",
        "reader": {"kind": "span", "span": "no.such.span"}}))
    (tmp_path / "gone_ms.json").write_text(json.dumps({
        "layer": "kernels", "unit": "ms", "moves": "allocs_per_s",
        "reader": {"kind": "kernel", "program": "renamed_away",
                   "field": "ms_per_launch"}}))
    obs = {"spans": {"durations": {"worker.snapshot": [0.001, 0.003, 0.002]},
                     "self": {}},
           "counters": {"applier": {"applied": 7}}, "client": {},
           "profile": {"programs": {}}}
    got = layers.read_all(["applier.plans", "custom", "nothing", "gone_ms"],
                          obs, root=tmp_path)
    assert got["applier.plans"]["value"] == 7.0
    assert got["custom"]["value"] == 1.0
    # nothing to read (no such span, a program the trace does not hold):
    # left out, never guessed
    assert "nothing" not in got and "gone_ms" not in got


def test_every_listed_layer_metric_has_its_reader_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    kept = [{"name": n, "unit": u, "moves": "allocs_per_s"}
            for n, u, _, _ in C2M_METRICS]
    for m in bench["per_layer"] + kept:
        spec = layers.load(m["name"])
        assert spec["unit"] == m["unit"]
        assert spec["layer"] == m.get("layer", spec["layer"])
        assert spec["moves"] == m["moves"]
        if spec["reader"]["kind"] == "kernel" \
                and spec["reader"]["field"] == "roofline_pct":
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
            assert (layers.KERNELS
                    / f"{spec['reader']['program']}.py").exists()
