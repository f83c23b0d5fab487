"""Trace reduction: busy union, idle share, per-program time, gap
labelling, on small recorded traces; the peaks table."""

from pathlib import Path

import pytest

from benchmark import layers, peaks, xplane as profile

DATA = Path(__file__).resolve().parent / "data"


def test_union_gaps_and_labels():
    busy = profile.merge([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert profile.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                             (4.0, 5.0)]
    assert profile.gaps(busy, 0.5, 3.5) == [(2.0, 3.0)]
    spans = [("worker.schedule", 2.0, 2.9), ("plan.verify", 2.1, 2.2),
             ("plan.verify", 2.3, 2.4)]
    assert profile.label_gap((2.0, 3.0), spans) == "worker.schedule"
    assert profile.label_gap((4.0, 5.0), spans) == "no span"
    # a span covering a sliver of the gap does not explain it
    assert profile.label_gap((0.0, 10.0), spans) == "no span"


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
    spans = [tuple(s) for s in want.get("spans", [])]
    spans = [(n, planes["clock"][1] + a, planes["clock"][1] + b)
             for n, a, b in spans]
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
    gap_total = sum(s for _, s in got["idle_gaps"])
    assert gap_total <= got["window_s"] - got["busy_s"] + 1e-9
    if spans:
        assert got["idle_gaps"][0][0] == want["top_gap_label"]


def test_a_recorded_trace_is_there():
    assert RECORDED, "benchmark/tests/data holds no recorded .xplane.pb"
