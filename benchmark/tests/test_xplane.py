"""Trace reduction: busy union, idle share, per-program time, gap
labelling, on small recorded traces; the peaks table."""

from pathlib import Path

import pytest

from benchmark import layers, peaks, xplane as profile

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps():
    busy = profile.merge([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert profile.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                             (4.0, 5.0)]
    assert profile.gaps(busy, 0.5, 3.5) == [(2.0, 3.0)]


def _holder(thread, t0, gather, ship, wait, end):
    """One evaluation on `thread`: queued, scheduled, waiting for the
    lock until `t0`, then the hold's phases up to `end`."""
    return [("eval.queued", t0 - 2.0, t0 - 1.5, thread),
            ("worker.schedule", t0 - 1.5, end + 0.5, thread),
            ("worker.solve", t0 - 1.0, end, thread),
            ("placer.lock_wait", t0 - 1.0, t0, thread),
            ("placer.locked", t0, end, thread),
            ("placer.gather", t0, gather, thread),
            ("placer.ship", gather, ship, thread),
            ("placer.device_wait", ship, wait, thread),
            ("placer.fetch", wait, end, thread)]


def test_a_gap_reads_what_the_launching_thread_was_inside():
    """24 workers hold `worker.schedule` open over the gap; the one that
    launches next is inside `placer.gather`: the gap reads that, not the
    span most threads hold."""
    crowd = [("worker.schedule", 0.0, 10.0, f"w{i}") for i in range(24)]
    crowd += [("placer.lock_wait", 0.5, 10.0, f"w{i}") for i in range(24)]
    spans = crowd + _holder("holder", 2.0, gather=3.0, ship=3.2, wait=3.6,
                            end=3.7)
    host = profile.HostTimeline(spans)
    assert host.launcher(2.0) == "holder"
    assert profile.label_gap((2.0, 3.0), host) == {
        "placer.gather": pytest.approx(1.0)}
    # a gap over several phases is cut along them, and sums to itself
    got = profile.label_gap((2.5, 3.1), host)
    assert got == {"placer.gather": pytest.approx(0.5),
                   "placer.ship": pytest.approx(0.1)}
    # no launch in the records after the gap's start: said, not guessed
    assert profile.label_gap((4.0, 5.0), host) == {
        "no_launch_follows": pytest.approx(1.0)}


def test_a_wait_is_named_a_wait_and_a_lock_wait_reads_its_holder():
    first = _holder("w1", 1.0, gather=1.2, ship=1.3, wait=2.0, end=2.4)
    # w2 waits for w1's lock until 2.5 (0.1 s of hand-over), then launches
    second = _holder("w2", 2.5, gather=2.8, ship=2.9, wait=3.5, end=3.6)
    host = profile.HostTimeline(first + second)
    # the device finished at 1.9 and starts again at 2.85, inside w2's ship
    got = profile.label_gap((1.9, 2.85), host)
    assert sum(got.values()) == pytest.approx(0.95)
    assert got == {"placer.device_wait": pytest.approx(0.1),   # w1 wakes
                   "placer.fetch": pytest.approx(0.4),         # w1 holds on
                   "wait.placer.lock_wait": pytest.approx(0.1),  # hand-over
                   "placer.gather": pytest.approx(0.3),
                   "placer.ship": pytest.approx(0.05)}
    # before its worker took it, the launcher's evaluation sat in the
    # broker; between that record and the next, nothing was open
    got = profile.label_gap((-1.0, 0.0), host)
    assert got == {"wait.eval.queued": pytest.approx(0.5),
                   "worker.schedule": pytest.approx(0.5)}
    assert profile.label_gap((-2.0, -1.0), host) == {
        "no_span": pytest.approx(1.0)}


def test_a_launch_already_open_at_the_gap_comes_before_the_next_to_start():
    """Two launches in flight: w2's `placer.ship` opened while the device
    still ran w1's program and is open over the whole gap; w3's opens
    inside the gap. The gap is w2's to explain."""
    spans = [("placer.ship", 1.0, 1.1, "w1"),
             ("placer.ship", 1.5, 2.3, "w2"),
             ("placer.gather", 2.05, 2.1, "w3"),
             ("placer.ship", 2.1, 2.4, "w3")]
    host = profile.HostTimeline(spans)
    assert host.launcher(2.0) == "w2"
    assert profile.label_gap((2.0, 2.2), host) == {
        "placer.ship": pytest.approx(0.2)}
    assert host.launcher(2.35) == "w3"
    # but a span that has seen a program start on the device since it
    # opened has made its launch and only outlasted it: w3 is next
    host = profile.HostTimeline(spans, device_starts=[0.9, 1.6])
    assert host.launcher(2.0) == "w3"
    assert profile.label_gap((2.0, 2.2), host) == {
        "no_span": pytest.approx(0.05), "placer.gather": pytest.approx(0.05),
        "placer.ship": pytest.approx(0.1)}


def test_span_roles_are_data_and_a_new_launch_site_is_a_new_file(tmp_path):
    roles = profile.span_roles()
    assert {"placer.ship", "solver.dispatch"} <= roles["launch"]
    assert {"placer.lock_wait", "placer.admit", "eval.queued",
            "solver.wait"} <= roles["wait"]
    assert roles["held_by"]["placer.lock_wait"] == "placer.locked"
    for path in profile.HOST_SPANS.glob("*.json"):
        (tmp_path / path.name).write_text(path.read_text())
    (tmp_path / "mesh.json").write_text(
        '{"launch": ["mesh.launch"], "wait": ["mesh.barrier"]}')
    roles = profile.span_roles(tmp_path)
    spans = [("mesh.barrier", 0.0, 0.6, "m"), ("mesh.launch", 0.6, 1.2, "m")]
    host = profile.HostTimeline(spans, roles)
    assert profile.label_gap((0.0, 1.0), host) == {
        "wait.mesh.barrier": pytest.approx(0.6),
        "mesh.launch": pytest.approx(0.4)}
    # the same spans under the committed files alone: no launch is known
    assert profile.label_gap((0.0, 1.0), profile.HostTimeline(spans)) == {
        "no_launch_follows": pytest.approx(1.0)}


def test_idle_gaps_keep_their_sum_when_the_labels_outnumber_the_list():
    totals = {f"s{i}": float(i) for i in range(1, 14)}
    rows = profile._top_gaps(totals, 1, 10)
    assert len(rows) == 10 and rows[0] == ["s13", 13.0]
    assert rows[-1] == ["other", 1.0 + 2.0 + 3.0 + 4.0]
    assert sum(s for _, s in rows) == pytest.approx(sum(totals.values()))
    assert profile._top_gaps({"a": 4.0, "b": 2.0}, 2, 10) == [["a", 2.0],
                                                              ["b", 1.0]]


def test_peaks_table_names_its_source_and_refuses_unknown_kinds():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


RUN = {"nodes": 10240, "device_kind": "TPU v5 lite", "spread_values": 25,
       "specs": [{"count": 300, "spread": {"attribute": "${meta.rack}"}},
                 {"count": 1200, "spread": {"attribute": "${meta.rack}"}},
                 {"count": 8, "spread": None}]}


def kernel_bytes(program):
    return layers._module(layers.KERNELS / f"{program}.py",
                          "k").launch_bytes(RUN)


def test_byte_models_read_their_widths_from_the_program():
    from nomad_tpu.structs.resources import RESOURCE_DIMS as d
    from nomad_tpu.tensor.solver import BulkSolverService as S

    n, g, c = 16384, S.G_PAD, S.CORRECTIONS
    # carry in and out, capacity, g masks and boosts, int16 counts out
    assert kernel_bytes("_solve_bulk_multi_impl") == (
        3 * n * d * 4 + g * n * (1 + 4 + 2) + g * (d * 4 + 12)
        + c * 4 + c * d * 4)
    # the scan: the mean over the jobs above the host cut-over
    per = [n * (2 * d + 6) * 4 + k * 2 * 4 + 2 * n * 4 + 2 * 32 * 4
           + 2 * 4 + (5 + d) * 4 + 3 * k * 4 for k in (512, 2048)]
    assert kernel_bytes("solve_task_group_fused") == sum(per) / 2


def test_kernel_reader_names_its_program_and_needs_known_peaks():
    obs = {"profile": {"programs": {"jit__solve_bulk_multi_impl":
                                    {"seconds": 0.2, "launches": 10}}},
           "run": RUN}
    ms = {"kind": "kernel", "program": "_solve_bulk_multi_impl",
          "field": "ms_per_launch"}
    roof = dict(ms, field="roofline_pct")
    assert layers.read_declared(ms, obs) == pytest.approx(20.0)
    assert layers.read_declared(roof, obs) == pytest.approx(
        100.0 * kernel_bytes("_solve_bulk_multi_impl") / 819e9 / 0.02)
    # another program took most of the device time: this metric is
    # still this program's
    obs["profile"]["programs"]["jit_other"] = {"seconds": 9.0, "launches": 1}
    assert layers.read_declared(ms, obs) == pytest.approx(20.0)
    # a program with no byte model gives no roofline share
    assert layers.read_declared(dict(roof, program="other"), obs) is None
    with pytest.raises(KeyError):
        layers.read_declared(roof, dict(obs, run=dict(
            RUN, device_kind="no such chip")))
    # rehearsal on the CPU: no device kind, no share
    assert layers.read_declared(roof, dict(obs, run=dict(
        RUN, device_kind=None))) is None


RECORDED = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_reducer_on_a_recorded_trace(path):
    """The recorded traces carry their expected numbers beside them
    (<name>.expect.json, written by hand from a reading of the trace)."""
    import json

    want = json.loads(path.with_suffix("").with_suffix(
        ".expect.json").read_text())
    planes = profile.read_planes(str(path))
    assert planes["clock"] is not None
    assert set(planes["devices"]) == set(want["device_planes"])
    lo = planes["clock"][1] + want["window_from_clock"][0]
    hi = planes["clock"][1] + want["window_from_clock"][1]
    spans = [(n, planes["clock"][1] + a, planes["clock"][1] + b, thread)
             for n, a, b, thread in want.get("spans", [])]
    got = profile.reduce_trace(str(path), (lo, hi), spans)
    assert got["clock_aligned"]
    assert got["window_s"] == pytest.approx(hi - lo)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert got["idle_pct"] == pytest.approx(
        100.0 * (1 - want["busy_s"] / (hi - lo)), rel=1e-6)
    for name, (seconds, launches) in want["programs"].items():
        assert got["programs"][name]["seconds"] == pytest.approx(
            seconds, rel=1e-6)
        assert got["programs"][name]["launches"] == launches
    assert 0 < got["busy_s"] < got["window_s"]
    # the labels' seconds are the idle time, all of it
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    assert got["idle_gaps"][0][0] == want["top_gap_label"]
    by_label = dict(got["idle_gaps"])
    for label, seconds in want["gap_seconds"].items():
        assert by_label[label] == pytest.approx(seconds, abs=2e-6), label


def test_a_recorded_trace_is_there():
    assert RECORDED, "benchmark/tests/data holds no recorded .xplane.pb"
