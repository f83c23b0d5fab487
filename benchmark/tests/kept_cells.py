"""A copy of the benchmark that also lists the cells kept as files.

`c2m.backlog` and, since PR 31, `grid.spread.1200` are in the tree as
files (configuration, traffic, readers) and listed in no BENCHMARK.json
until the parent runs them steadily (PERF.md sections 2 and 4). This
puts their entries back in a copy, touching no file of code: the test
of the data-driven harness runs them from there at --toy size, and a
session that wants to measure one on the chip does the same by hand:

    python3 benchmark/tests/kept_cells.py .bench_check/kept
    cd .bench_check/kept && PYTHONPATH=<checkout> python3 \\
        benchmark/run.py --workload grid.spread.1200 --seed <n> \\
        --seconds 30 --trace 0

With `--control` after the directory the copy is the control of the
fitness comparison instead (`write_control`).
"""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# The entry `grid.spread.1200` comes back with (PERF.md section 2, the
# rule of PR 31): its `why` says what the cell measures since PR 29.
SPREAD_1200 = {
    "name": "grid.spread.1200", "config": "grid-10k",
    "traffic": "spread.1200", "chips": 1,
    "why": "empty cluster, 12 service jobs x 1,200 allocs with the rack "
           "spread (14,400 = 72% of what fits): 12 racing evaluations of "
           "upstream's largest job; commit path, post-solve loop "
           "under 2,048-step programs"}

C2M_METRICS = [("solver.wait_ms", "ms", "lower", "program_span"),
               ("solver.evals_per_launch", "evals", "higher",
                "program_counter"),
               ("solver.resyncs", "count", "lower", "program_counter"),
               ("solve_bulk_multi_ms", "ms", "lower", "device_trace"),
               ("solve_bulk_multi_roofline", "%", "higher", "device_trace")]


def write_copy(root: Path, extra_metrics=()) -> dict:
    """`root`/benchmark and `root`/BENCHMARK.json with both kept cells
    listed: `c2m.backlog` with its configuration and its readers (and
    `extra_metrics`, rows like C2M_METRICS whose files the caller has put
    or will put into the copy), `grid.spread.1200` as one entry and its
    name on every per-layer list that names `grid.spread.300`. -> the
    copy's BENCHMARK.json."""
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns(".work", "__pycache__",
                                                  "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads(
        (ROOT / "benchmark/configs/c2m-10k.json").read_text())
    bench["configs"].append({
        "name": "c2m-10k", "source": config["source"],
        "file": "benchmark/configs/c2m-10k.json",
        "reduced": config["reduced"], "why": "kept as files"})
    bench["workloads"].append({
        "name": "c2m.backlog", "config": "c2m-10k", "traffic": "backlog",
        "chips": 1, "why": "kept as files"})
    bench["workloads"].append(SPREAD_1200)
    for m in bench["per_layer"]:
        if "grid.spread.300" in m.get("workloads", ()):
            m["workloads"].append("grid.spread.1200")
    for name, unit, better, source, layer in extra_metrics + tuple(
            row + (json.loads((ROOT / "benchmark/layer_metrics"
                               / f"{row[0]}.json").read_text())["layer"],)
            for row in C2M_METRICS):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "allocs_per_s",
            "workloads": ["c2m.backlog"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return bench


def write_control(root: Path) -> None:
    """The control of `fitness_under_reference`: a copy whose grid
    configurations start their agent with `--algorithm spread`, the
    program's own worst-fit arm (upstream's spread algorithm), where
    they state `tpu-binpack`; one round a run. Every other number of
    `correct` holds in it; the fitness reads far under the reference
    (PERF.md section 2 has the chip's readings beside the limit)."""
    write_copy(root)
    for name in ("grid-10k", "grid-10k-r3"):
        path = root / "benchmark/configs" / f"{name}.json"
        config = json.loads(path.read_text())
        config["agent"]["algorithm"] = "spread"
        config.pop("window", None)
        config.get("toy", {}).pop("window", None)
        path.write_text(json.dumps(config, indent=1))


if __name__ == "__main__":
    if len(sys.argv) not in (2, 3) or sys.argv[2:] not in ([], ["--control"]):
        sys.exit(__doc__)
    (write_control if sys.argv[2:] else write_copy)(Path(sys.argv[1]))
