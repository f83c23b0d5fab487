"""Per-layer metrics: one small reader per metric, found by name.

`benchmark/layer_metrics/<name>.json` holds the metric's layer, unit,
the end-to-end metric it should move, and a declarative reader:

    {"kind": "span", "span": "eval.queued", "stat": "median",
     "self": false, "scale": 1000}
    {"kind": "counter", "num": ["solver.solves"],
     "den": ["solver.launches"], "scale": 1}
    {"kind": "profile", "field": "idle_pct"}
    {"kind": "client", "field": "out_of_attempts_pct"}
    {"kind": "kernel", "program": "solve_task_group_fused",
     "field": "ms_per_launch" | "roofline_pct"}

A `kernel` reader names one jitted program: its device time and
launches come from the profiler trace, and for the roofline share its
bytes a launch from `benchmark/kernels/<program>.py` (`launch_bytes(run)`,
found by name) over the peak bandwidth of `benchmark/peaks.py`. A
program the trace does not hold, or one with no byte model, gives
nothing; a device kind the peaks table does not hold is an error.

`<name>.py` beside it, with `read(observations) -> float | None`, takes
over where that is not enough. A reader that finds nothing to read
returns None and the harness leaves the metric out of the line; adding
a metric is a new file plus an entry in BENCHMARK.json, never an edit.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from benchmark.observe import stat

HERE = Path(__file__).resolve().parent / "layer_metrics"
KERNELS = Path(__file__).resolve().parent / "kernels"


def _lookup(tree: dict, path: str):
    group, _, key = path.partition(".")
    return tree[group][key]


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_kernel(reader: dict, obs: dict, kernels: Path = KERNELS):
    """ms a launch, or the share of the bytes-once roofline, of the one
    jitted program the reader names."""
    program = reader["program"]
    row = obs["profile"].get("programs", {}).get(f"jit_{program}")
    if not row or not row["launches"] or not row["seconds"]:
        return None
    per_launch = row["seconds"] / row["launches"]
    if reader["field"] == "ms_per_launch":
        return 1e3 * per_launch
    if reader["field"] != "roofline_pct":
        raise ValueError(f"unknown kernel field {reader['field']!r}")
    model = kernels / f"{program}.py"
    run = obs.get("run", {})
    if not model.exists() or run.get("device_kind") is None:
        return None
    from benchmark.peaks import peaks_for

    nbytes = _module(model, f"benchmark_kernel_{program}").launch_bytes(run)
    if nbytes is None:
        return None
    floor_s = nbytes / peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / per_launch


def read_declared(reader: dict, obs: dict):
    kind = reader["kind"]
    scale = float(reader.get("scale", 1.0))
    if kind == "kernel":
        return read_kernel(reader, obs)
    try:
        if kind == "span":
            table = obs["spans"]["self" if reader.get("self") else "durations"]
            return scale * stat(table[reader["span"]],
                                reader.get("stat", "median"))
        if kind == "counter":
            num = sum(_lookup(obs["counters"], p) for p in reader["num"])
            if "den" not in reader:
                return scale * num
            den = sum(_lookup(obs["counters"], p) for p in reader["den"])
            return scale * num / den if den else None
        if kind in ("profile", "client"):
            value = obs[kind][reader["field"]]
            return None if value is None else scale * float(value)
    except (KeyError, LookupError, TypeError):
        return None
    raise ValueError(f"unknown reader kind {kind!r}")


def load(name: str, root: Path = HERE) -> dict:
    with open(root / f"{name}.json") as f:
        spec = json.load(f)
    code = root / f"{name}.py"
    if code.exists():
        spec["_read"] = _module(
            code, f"benchmark_layer_metric_{name.replace('.', '_')}").read
    return spec


def read_all(names, obs: dict, root: Path = HERE) -> dict:
    """-> {name: {"value", "unit"}} for every metric whose reader found
    something."""
    out = {}
    for name in names:
        spec = load(name, root)
        value = (spec["_read"](obs) if "_read" in spec
                 else read_declared(spec["reader"], obs))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out
