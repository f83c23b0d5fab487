"""`placer.stage_ms` (PR 26): the median of the `placer.stage` span, the
packing and shipping of the fused solve's static arguments that left
`_PER_EVAL_SOLVE_LOCK`. A program without the span (the parent) gives
nothing to read, and the toy traced run of each cell reports it."""

import pytest

from benchmark import layers
from benchmark.harness import metrics_of
from benchmark.tests.test_contract import BENCH, CELLS, run_cell

NAME = "placer.stage_ms"


def test_the_metric_is_listed_and_its_file_is_data():
    m = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    assert m["workloads"] == CELLS
    assert m["source"] == "program_span"
    spec = layers.load(NAME)
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        m["unit"], m["layer"], m["moves"])
    assert spec["reader"] == {"kind": "span", "span": "placer.stage",
                              "stat": "median", "scale": 1000}
    assert not (layers.HERE / f"{NAME}.py").exists()
    # beside the phases it took the work from, in the same layer
    assert spec["layer"] == layers.load("placer.pack_ms")["layer"]


def _spans(records):
    """What observe.spans_in_window hands the readers, from records of
    (name, start, end)."""
    durations: dict = {}
    for name, t0, t1 in records:
        durations.setdefault(name, []).append(t1 - t0)
    return {"spans": {"durations": durations, "self": durations}}


def test_reads_the_median_stage_in_ms_and_nothing_from_the_parent():
    recorded = [("placer.stage", 0.000, 0.012), ("placer.stage", 0.1, 0.13),
                ("placer.stage", 0.2, 0.207), ("placer.pack", 0.3, 0.301),
                ("placer.locked", 0.3, 0.4), ("worker.solve", 0.21, 0.4)]
    got = layers.read_all([NAME, "placer.pack_ms"], _spans(recorded))
    assert got[NAME] == {"value": pytest.approx(12.0), "unit": "ms"}
    assert got["placer.pack_ms"]["value"] == pytest.approx(1.0)
    # the parent's program records every phase but this one
    parent = [r for r in recorded if r[0] != "placer.stage"]
    assert layers.read_all([NAME], _spans(parent)) == {}
    assert layers.read_declared(layers.load(NAME)["reader"],
                                _spans(parent)) is None
    # an untraced run hands the readers no spans at all
    assert layers.read_all([NAME], {"spans": {"durations": {},
                                              "self": {}}}) == {}


def assert_stage_metric(line: dict, out: str, names: list) -> None:
    stage = line["metrics"][NAME]
    assert stage["unit"] == "ms" and stage["value"] > 0
    # the phases under the lock keep reading: same spans, same names
    for name in names:
        assert name in line["metrics"], name
    # one stage a hold
    spans = next(l for l in out.splitlines() if l.startswith("[spans]"))
    assert "placer.stage" in spans


@pytest.mark.parametrize("cell", CELLS)
def test_toy_traced_cell_reports_the_stage(cell):
    line, out, _ = run_cell(cell, trace=1)
    assert_stage_metric(line, out, [
        m["name"] for m in metrics_of(BENCH, "per_layer", cell)
        if m["name"] in ("placer.gather_ms", "placer.pack_ms",
                         "placer.ship_ms", "placer.device_wait_ms",
                         "placer.host_locked_pct")])
