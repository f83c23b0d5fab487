"""`placer.columnar_groups_pct` (PR 34): of the groups the per-placement
tier staged in the window, the share it handed over as one AllocBlock
(`nomad.placer.columnar_scan_groups` over `nomad.placer.staged_solves`).
Data only. A program without the counter (the parent) gives nothing to
read; a traced run of each listed cell reports it (100 at the cell's
own size, where every evaluation is the grid's job)."""

import pytest

from benchmark import layers
from benchmark.tests.test_contract import BENCH, CELLS, run_cell

NAME = "placer.columnar_groups_pct"


def test_the_metric_is_listed_last_and_its_file_is_data():
    m = BENCH["per_layer"][-1]
    assert m["name"] == NAME and m["workloads"] == CELLS
    assert (m["source"], m["better"]) == ("program_counter", "higher")
    spec = layers.load(NAME)
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        m["unit"], m["layer"], m["moves"]) == (
            "%", "per-placement tier", "allocs_per_s")
    assert spec["reader"] == {
        "kind": "counter",
        "num": ["registry.nomad.placer.columnar_scan_groups"],
        "den": ["registry.nomad.placer.staged_solves"], "scale": 100}
    assert not (layers.HERE / f"{NAME}.py").exists()
    assert spec["layer"] == layers.load("placer.scan_steps_run_pct")["layer"]


def test_reads_the_share_and_nothing_from_the_parent():
    def obs(**registry):
        return {"counters": {"registry": registry}}

    got = layers.read_all([NAME], obs(**{
        "nomad.placer.columnar_scan_groups": 45,
        "nomad.placer.staged_solves": 50}))
    assert got[NAME] == {"value": pytest.approx(90.0), "unit": "%"}
    # the parent's program stages and has no such counter
    assert layers.read_all(
        [NAME], obs(**{"nomad.placer.staged_solves": 50})) == {}
    # nothing staged in the window: no share
    assert layers.read_all([NAME], obs(**{
        "nomad.placer.columnar_scan_groups": 0,
        "nomad.placer.staged_solves": 0})) == {}


@pytest.mark.parametrize("cell", CELLS)
def test_toy_traced_cell_reports_the_share(cell):
    """At --toy size a job has 24 placements, under the 256 from which
    the reconciler hands a group over in bulk: every group is staged,
    none is columnar, and the share reads 0 (on the chip, at the cell's
    own 300, 100)."""
    line, _, _ = run_cell(cell, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][NAME] == {"value": 0.0, "unit": "%"}
    assert line["metrics"]["placer.scan_steps_run_pct"]["value"] > 0
