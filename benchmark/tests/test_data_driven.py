"""A cell, a configuration or a per-layer metric dropped into the
directories is found with no edit to code."""

import json
from pathlib import Path

import pytest

from benchmark import layers
from benchmark.tests.kept_cells import (C2M_METRICS, SPREAD_1200,
                                        write_copy)
from benchmark.tests.test_contract import run_cell
from benchmark.tests.test_phase_metrics import (assert_phase_metrics,
                                                phase_metrics_of)
from benchmark.tests.test_stage_metric import assert_stage_metric

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def copy_with_the_kept_cells(tmp_path_factory):
    """`kept_cells.write_copy` plus one new layer-metric file and its
    entry: a cell and a metric are files and entries, never code."""
    root = tmp_path_factory.mktemp("kept") / "copy"
    bench = write_copy(root, extra_metrics=(
        ("worker.snapshot_ms", "ms", "lower", "program_span",
         "scheduler worker"),))
    (root / "benchmark/layer_metrics/worker.snapshot_ms.json").write_text(
        json.dumps({"layer": "scheduler worker", "unit": "ms",
                    "moves": "allocs_per_s",
                    "reader": {"kind": "span", "span": "worker.snapshot",
                               "stat": "median", "scale": 1000}}))
    return root, bench


@pytest.mark.parametrize("cell", ["c2m.backlog", "grid.spread.1200"])
def test_a_kept_cell_is_files_and_entries(copy_with_the_kept_cells, cell):
    """`c2m.backlog` and, since PR 31, `grid.spread.1200` are kept as
    files and listed in no BENCHMARK.json until the parent runs them
    steadily (PERF.md sections 2 and 4). With its entries put back in a
    copy, each runs (at --toy size) with no edit to code, and its traced
    line carries its metrics."""
    root, bench = copy_with_the_kept_cells
    assert len(SPREAD_1200["why"]) <= 200
    line, out, _ = run_cell(cell, trace=1, root=root)
    assert line["correct"] is True and line["failed"] == 0
    if cell == "c2m.backlog":
        assert line["attempted"] == 24
        for name in ("worker.snapshot_ms", "solver.wait_ms",
                     "solver.evals_per_launch", "solve_bulk_multi_ms"):
            assert line["metrics"][name]["value"] > 0, name
        return
    # the phase spans of PR 25 and the stage of PR 26, as in the two
    # listed cells of its configuration
    assert_phase_metrics(line, phase_metrics_of(cell, bench))
    assert_stage_metric(line, out, ["placer.gather_ms", "placer.ship_ms",
                                    "placer.host_locked_pct"])
    assert line["metrics"]["placer.scan_steps_run_pct"]["value"] > 0


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
