"""The per-layer metrics over the phase spans of the two critical
sections (PR 25): every file loads, a program without the spans gives
nothing to read, and the toy traced run of each listed cell reports those
that list it. Entries are found by name, wherever later PRs appended."""

import pytest

from benchmark import layers
from benchmark.harness import metrics_of
from benchmark.tests.test_contract import BENCH, CELLS, run_cell

PLACER_MS = ["placer.lock_wait_ms", "placer.gather_ms", "placer.pack_ms",
             "placer.ship_ms", "placer.device_wait_ms", "placer.fetch_ms",
             "placer.register_ms"]
NEW = PLACER_MS + ["placer.host_locked_pct", "store.lock_wait_ms",
                   "store.apply_ms", "store.publish_ms",
                   "store.commit_offcpu_pct", "applier.rows_rejected_pct"]


# where a metric's own `workloads` list leaves a listed cell out, and
# why (PERF.md section 3)
NOT_IN = {"placer.host_locked_pct": {"grid.r3.spread.300"}}


def test_every_new_metric_is_listed_and_its_file_loads():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = listed[name]
        spec = layers.load(name)
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])
        assert m["workloads"] == [c for c in CELLS
                                  if c not in NOT_IN.get(name, ())]
        code = layers.HERE / f"{name}.py"
        assert ("_read" in spec) == code.exists()
        assert spec["reader"]["kind"] == ("code" if code.exists() else
                                          "counter" if m["source"] ==
                                          "program_counter" else "span")


def _rec(name, t0, t1, **args):
    return (name, None, 0, 1, t0, t1, "t", args)


def test_host_locked_pct_sums_the_host_phases_over_the_traced_window():
    records = [_rec("placer.gather", 0.0, 0.1), _rec("placer.pack", 0.1, 0.2),
               _rec("placer.ship", 0.2, 0.3),
               _rec("placer.device_wait", 0.3, 0.8),   # the device's part
               _rec("placer.fetch", 0.8, 0.9),
               _rec("placer.register", 0.9, 1.0),
               _rec("placer.locked", 0.0, 1.0), _rec("worker.solve", 0.0, 1.5)]
    obs = {"spans": {"records": records}, "profile": {"window_s": 2.0}}
    got = layers.read_all(["placer.host_locked_pct"], obs)
    assert got["placer.host_locked_pct"]["value"] == pytest.approx(25.0)
    # the parent's program has no such span; an untraced run no window
    assert layers.read_all(["placer.host_locked_pct"], {
        "spans": {"records": [_rec("worker.solve", 0.0, 1.5)]},
        "profile": {"window_s": 2.0}}) == {}
    assert layers.read_all(["placer.host_locked_pct"], {
        "spans": {"records": records}, "profile": {}}) == {}


def test_commit_offcpu_pct_reads_cpu_s_and_nothing_without_it():
    obs = {"spans": {"records": [
        _rec("plan.commit_round", 0.0, 1.0, cpu_s=0.25, n=1),
        _rec("plan.commit_round", 2.0, 3.0, cpu_s=0.75, n=1),
        _rec("plan.verify", 0.0, 9.0, cpu_s=0.0)]}}
    got = layers.read_all(["store.commit_offcpu_pct"], obs)
    assert got["store.commit_offcpu_pct"]["value"] == pytest.approx(50.0)
    parent = {"spans": {"records": [_rec("plan.commit_round", 0.0, 1.0, n=1)]}}
    assert layers.read_all(["store.commit_offcpu_pct"], parent) == {}
    assert layers.read_all(["store.commit_offcpu_pct"],
                           {"spans": {"records": []}}) == {}


def test_rows_rejected_pct_is_a_share_of_rows_verified():
    obs = {"counters": {"applier": {"nodes_rejected": 3,
                                    "nodes_verified": 60}}}
    got = layers.read_all(["applier.rows_rejected_pct"], obs)
    assert got["applier.rows_rejected_pct"]["value"] == pytest.approx(5.0)
    parent = {"counters": {"applier": {"nodes_rejected": 3}}}
    assert layers.read_all(["applier.rows_rejected_pct"], parent) == {}


def phase_metrics_of(cell: str, bench: dict = BENCH) -> list:
    """PR 25's metrics that list `cell`, by each metric's own list."""
    return [m["name"] for m in metrics_of(bench, "per_layer", cell)
            if m["name"] in NEW]


def assert_phase_metrics(line: dict, names: list) -> None:
    metrics = line["metrics"]
    for name in names:
        assert name in metrics, name
    for name in PLACER_MS:
        assert metrics[name]["value"] > 0 and metrics[name]["unit"] == "ms"
    for name in ("placer.host_locked_pct", "store.commit_offcpu_pct",
                 "applier.rows_rejected_pct"):
        if name in names:
            assert 0 <= metrics[name]["value"] <= 100
    # the phases are parts of the span the accepted metric reads
    parts = sum(metrics[n]["value"] for n in PLACER_MS)
    assert metrics["placer.solve_ms"]["value"] > 0 and parts > 0


@pytest.mark.parametrize("cell", CELLS)
def test_toy_traced_cell_reports_the_phase_metrics(cell):
    line, _, _ = run_cell(cell, trace=1)
    names = phase_metrics_of(cell)
    assert set(PLACER_MS) <= set(names)
    assert_phase_metrics(line, names)
