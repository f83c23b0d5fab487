"""Plain reference for the system job's victims: upstream's rule, per
node, over plain data.

"A node loses only what upstream's rule takes, and nothing if it had
room" (scheduler/preemption.go PreemptForTaskGroup, as quoted from
memory in configs/grid-10k-system.json):

1. candidates are allocations at least `delta` (10) under the asking
   job's priority;
2. they are taken in priority groups, lowest first;
3. within a group, the one whose resources come closest to what is
   still missing (basicResourceDistance: the Euclidean distance of the
   normalized difference over the dimensions still short) plus the
   max_parallel penalty (50 for each eviction of its group past the
   group's max_parallel) goes first, ties to the lower allocation id;
4. once the ask fits, filterSuperset drops each victim, highest
   priority first, that the ask does not need.

Numpy arrays and loops; nothing here imports the program (its
scheduler/preemption.py least of all: that is the code under test).

    cap     (D,) what a new placement may use of the node
    allocs  [{"id", "priority", "vec": (D,), "max_parallel", "group"}]
            the node's live allocations at the round's opening
    ask     (D,) the system group's ask

`choose()` returns the victims' ids (None when no set of victims makes
room), [] for a node with room.
"""

from __future__ import annotations

import math

import numpy as np

PRIORITY_DELTA = 10
MAX_PARALLEL_PENALTY = 50.0


def _fits(cap, allocs, gone, ask) -> bool:
    used = np.zeros_like(cap)
    for a in allocs:
        if a["id"] not in gone:
            used = used + a["vec"]
    return bool(np.all(used + ask <= cap))


def _distance(need, have) -> float:
    total = 0.0
    for n, h in zip(need.tolist(), have.tolist()):
        if n > 0:
            total += ((h - n) / n) ** 2
    return math.sqrt(total)


def choose(cap, allocs: list, ask, priority: int,
           counts: dict = None):
    """The victims upstream's rule takes on one node (module doc);
    `counts` holds the evictions of each group already made by the plan
    (it is updated with this node's)."""
    cap = np.asarray(cap, np.float64)
    ask = np.asarray(ask, np.float64)
    allocs = [dict(a, vec=np.asarray(a["vec"], np.float64)) for a in allocs]
    if _fits(cap, allocs, set(), ask):
        return []
    counts = {} if counts is None else counts
    mine = dict(counts)
    cands = sorted((a for a in allocs
                    if priority - a["priority"] >= PRIORITY_DELTA),
                   key=lambda a: (a["priority"], a["id"]))
    gone: set = set()
    picked: list = []
    i = 0
    while i < len(cands):
        group = [a for a in cands[i:] if a["priority"] == cands[i]["priority"]]
        i += len(group)
        while group:
            used = np.zeros_like(cap)
            for a in allocs:
                if a["id"] not in gone:
                    used = used + a["vec"]
            need = np.maximum(used + ask - cap, 0.0)

            def score(a):
                n = mine.get(a["group"], 0)
                mp = int(a.get("max_parallel", 0))
                pen = ((n + 1 - mp) * MAX_PARALLEL_PENALTY
                       if mp > 0 and n >= mp else 0.0)
                return _distance(need, a["vec"]) + pen

            best = min(range(len(group)), key=lambda k: (score(group[k]), k))
            a = group.pop(best)
            picked.append(a)
            gone.add(a["id"])
            mine[a["group"]] = mine.get(a["group"], 0) + 1
            if _fits(cap, allocs, gone, ask):
                for v in sorted(picked, key=lambda a: -a["priority"]):
                    gone.discard(v["id"])
                    if not _fits(cap, allocs, gone, ask):
                        gone.add(v["id"])
                out = [v["id"] for v in picked if v["id"] in gone]
                for v in picked:
                    if v["id"] in gone:
                        counts[v["group"]] = counts.get(v["group"], 0) + 1
                return out
    return None
