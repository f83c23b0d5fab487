"""The one traffic generator: a traffic file's parameters + a seed ->
job specs.

Pure (random.Random only, no clock, no program import): the same file
and seed give the same specs byte for byte, in this process and in the
load generator's child. A spec is a plain dict

    {"id", "type": "service"|"batch", "count", "cpu", "mem",
     "ports": n dynamic ports, "spread": {"attribute", "weight"} | None,
     and the class's "datacenters" / "constraints" where it names them}

and `benchmark/jobs.py` turns it into the program's Job.

A traffic file names job `classes`, each with a `share`, a job `type`,
a `count` rule ({"cycle": [...]} by job number within the class, or
{"buckets": [[lo, hi, share], ...]}, log-uniform inside a bucket), `cpu`
and `mem` as [lo, hi] (log-uniform; lo == hi is a constant), `ports`,
and an optional `spread` ({"attribute", "weight"}).
"""

from __future__ import annotations

import math
import random
from typing import Dict, List, Optional


def _log_quantile(lo: float, hi: float, q: float) -> int:
    """The q-quantile (0 <= q < 1) of a log-uniform draw from [lo, hi]."""
    if lo >= hi:
        return int(lo)
    return int(round(math.exp(math.log(lo) + q * (math.log(hi)
                                                   - math.log(lo)))))


def count_at(rule: dict, q: float, number: int) -> int:
    """The q-quantile of a count rule: a cycle goes by job number, a
    bucket mixture by its cumulative shares, log-uniform inside."""
    if "cycle" in rule:
        return int(rule["cycle"][number % len(rule["cycle"])])
    buckets = rule["buckets"]
    total = sum(b[2] for b in buckets)
    acc = 0.0
    for lo, hi, share in buckets:
        w = share / total
        if q < acc + w or (lo, hi, share) == tuple(buckets[-1]):
            inner = min(max((q - acc) / w, 0.0), 1.0 - 1e-12)
            return max(int(lo), min(int(hi), _log_quantile(lo, hi, inner)))
        acc += w
    raise AssertionError("unreachable")


def count_range(rule: dict) -> tuple:
    if "cycle" in rule:
        return min(rule["cycle"]), max(rule["cycle"])
    return (min(b[0] for b in rule["buckets"]),
            max(b[1] for b in rule["buckets"]))


def spread_for(cls: dict) -> Optional[dict]:
    sp = cls.get("spread")
    if not sp:
        return None
    return {"attribute": sp["attribute"], "weight": int(sp["weight"])}


def placement_of(cls: dict) -> dict:
    """The class's `datacenters` and `constraints`, where it names them
    (a job without them keeps the mock job's own)."""
    return {k: cls[k] for k in ("datacenters", "constraints") if k in cls}


def _strata(rng: random.Random, n: int) -> List[float]:
    """n evenly spaced quantiles (mid-points), in a seeded order."""
    qs = [(i + 0.5) / n for i in range(n)]
    rng.shuffle(qs)
    return qs


def job_specs(traffic: dict, seed: int, n: int, prefix: str) -> List[dict]:
    """`n` job specs in submit order, a pure function of the arguments.

    Stratified: every seed draws the same amount of work. The classes
    get their exact shares of the n jobs, and within a class the counts,
    cpu and memory asks sit at evenly spaced quantiles of their
    distributions; the seed decides only which job gets which value and
    in which order they come. A run's tail then depends on how the
    system handles the large jobs, not on whether the draw held one."""
    rng = random.Random(f"jobs-{seed}")
    classes = traffic["classes"]
    shares = [float(c.get("share", 1.0)) for c in classes]
    total = sum(shares)
    # largest-remainder split of n over the classes
    exact = [n * s / total for s in shares]
    sizes = [int(x) for x in exact]
    for i in sorted(range(len(classes)), key=lambda i: exact[i] - sizes[i],
                    reverse=True)[:n - sum(sizes)]:
        sizes[i] += 1
    slots = [ci for ci, k in enumerate(sizes) for _ in range(k)]
    if len(classes) > 1:
        rng.shuffle(slots)
    draws = []
    for cls, k in zip(classes, sizes):
        draws.append({"count": _strata(rng, k), "cpu": _strata(rng, k),
                      "mem": _strata(rng, k), "at": 0})
    out = []
    for i, ci in enumerate(slots):
        cls, d = classes[ci], draws[ci]
        j = d["at"]
        d["at"] += 1
        count = count_at(cls["count"], d["count"][j], j)
        out.append({"id": f"{prefix}-{i:05d}", "type": cls["type"],
                    "count": count,
                    "cpu": _log_quantile(*cls["cpu"], d["cpu"][j]),
                    "mem": _log_quantile(*cls["mem"], d["mem"][j]),
                    "ports": int(cls.get("ports", 0)),
                    "spread": spread_for(cls), **placement_of(cls)})
    return out


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def warm_specs(traffic: dict, prefix: str) -> Dict[str, List[dict]]:
    """One small job per compiled shape the traffic's counts can reach.

    The per-placement scan compiles once per power-of-two padded length
    and per (spread, ports) variant; the count solve compiles once per
    launch width (one eval alone, a full burst). So: for every class and
    spread variant, one job per power of two between its smallest and
    largest count ("singles", each a shape of its own), and for a class
    the count solve can take (no port, no spread) one burst of
    `burst_jobs` jobs of `burst_count` to reach the full launch width.
    Counts up to `host_cutover` compile nothing (the host scores them)
    and are skipped. Asks are 1 MHz / 1 MB: the shape does not depend on
    them and the cluster barely notices."""
    singles, burst, seen = [], [], set()
    warm = traffic.get("warm", {})
    bulk_count = int(warm.get("burst_count", 256))
    skip_upto = int(warm.get("host_cutover", 0))
    def single(ci, cls, count, tag):
        spread = spread_for(cls)
        # ports are assigned on the host after the solve and change no
        # compiled shape; a warm job keeps its port only where dropping
        # it would send the job down the count solve instead
        keep_port = spread is None and count >= bulk_count
        return {"id": f"{prefix}-c{ci}-{tag}", "type": cls["type"],
                "count": count, "cpu": 1, "mem": 1,
                "ports": int(cls.get("ports", 0)) if keep_port else 0,
                "spread": spread, **placement_of(cls)}

    for ci, cls in enumerate(traffic["classes"]):
        lo, hi = count_range(cls["count"])
        ports = int(cls.get("ports", 0))
        wanted = [hi] + [c for c in warm.get("remainder_counts", ())
                         if c < hi]
        p = _pow2_at_least(max(1, lo))
        while p < hi:
            wanted.append(p)
            p *= 2
        for count in sorted(set(wanted)):
            plain = not ports and spread_for(cls) is None
            if plain and count >= bulk_count:
                # the count solve: one shape whatever the count
                key, tag = "bulk", "bulk"
                if not burst:
                    burst = [dict(single(ci, cls, bulk_count, f"b{i:03d}"))
                             for i in range(int(warm.get("burst_jobs", 32)))]
                if count == bulk_count and bulk_count - 1 >= lo:
                    # ... and the scan just under it pads to the same length
                    wanted_scan = bulk_count - 1
                    k2 = (False, _pow2_at_least(wanted_scan))
                    if k2 not in seen and wanted_scan > skip_upto:
                        seen.add(k2)
                        singles.append(single(ci, cls, wanted_scan,
                                              f"k{wanted_scan}"))
            else:
                key = (spread_for(cls) is not None,
                       _pow2_at_least(count))
                tag = f"k{count}"
            if key not in seen and count > skip_upto:
                seen.add(key)
                singles.append(single(ci, cls, count, tag))
    return {"singles": singles, "burst": burst}
