"""`correct`: the end state against the configuration's guarantees and
the plain reference.

Two halves. `end_state()` reads the store through its indexes into a
plain dict (no Python pass over 2M allocation rows: AllocBlocks are
read as columns); `judge()` decides from that dict alone, so a test can
doctor a state and see it refused. What is held:

- every job the client saw complete has exactly `count` live allocations;
- on a seeded sample of nodes, summed `allocated_vec` <= capacity in
  every dimension and no port value twice; no node down anywhere;
- a spread job's per-value counts are even: max - min within
  `spread_abs_tol` + `spread_rel_tol` x the mean (what racing
  evaluations reach; one job at a time reaches max - min <= 1);
- the mean BestFit fitness of the cell's placements (final-usage form)
  is at least the reference's, less `fitness_rel_tol` relative;
- the solver retraced nothing and lost no twin; no ERROR log record,
  no uncaught thread exception.
"""

from __future__ import annotations

import random

import numpy as np

from benchmark.reference import reference_fitness
from benchmark.reference.fitness import mean_fitness


def live_count(snap, job_id: str, namespace: str = "default") -> int:
    """Live allocations of a job by its index entry: a block counts its
    live size, a row counts unless terminal."""
    from nomad_tpu.state.store import BlockRef

    store, n = snap._store, 0
    for entry in snap._ids_from_index(store._allocs_by_job,
                                      (namespace, job_id)):
        if type(entry) is BlockRef:
            block = store._alloc_blocks.get(entry.block_id, snap.index)
            if block is not None:
                n += block.live_size()
        else:
            a = store._allocs.get(entry, snap.index)
            if a is not None and not a.terminal_status():
                n += 1
    return n


def node_census(snap, node) -> dict:
    """One node, recounted from its index entry: summed allocated_vec
    and every port value handed out on it."""
    from nomad_tpu.state.store import BlockRef

    store = snap._store
    used = np.zeros_like(node.available_vec(), dtype=np.float64)
    ports = []
    for entry in snap._ids_from_index(store._allocs_by_node, node.id):
        if type(entry) is BlockRef:
            block = store._alloc_blocks.get(entry.block_id, snap.index)
            if block is None or entry.row in block.rejected_rows:
                continue
            used += block.allocated_vec * float(block.counts[entry.row])
            continue
        a = store._allocs.get(entry, snap.index)
        if a is None or a.terminal_status():
            continue
        used += a.allocated_vec
        ports.extend(p.value for p in a.allocated_ports or ())
    return {"id": node.id, "cap": node.available_vec().astype(np.float64),
            "used": used, "ports": ports}


def spread_attribute(traffic: dict) -> str:
    """The attribute the traffic's spread jobs name ("${meta.rack}"), or
    "" when none spreads."""
    for cls in traffic["classes"]:
        if cls.get("spread"):
            return cls["spread"]["attribute"]
    return ""


def _value(node, attribute: str) -> str:
    """"${meta.rack}" / "${attr.rack}" read off a node; "" if unset."""
    kind, _, key = attribute.strip("${}").partition(".")
    table = node.meta if kind == "meta" else node.attributes
    return table.get(key, "")


def cluster_arrays(snap, attribute: str) -> dict:
    """The fleet in id order: capacity (cpu, mem), the store's usage
    table (cpu, mem), readiness, the spread attribute's value index."""
    nodes = sorted(snap.nodes(), key=lambda n: n.id)
    cap = np.array([n.available_vec()[:2] for n in nodes], np.float64)
    used = np.zeros_like(cap)
    for i, n in enumerate(nodes):
        u = snap.node_usage(n.id)
        if u is not None:
            used[i] = u[:2]
    of = [_value(n, attribute) for n in nodes]
    values = sorted(set(of))
    vi = {v: i for i, v in enumerate(values)}
    return {"nodes": nodes, "ids": [n.id for n in nodes], "cap": cap,
            "used": used, "values": values,
            "value_of": np.array([vi[v] for v in of], np.int64),
            "not_ready": [n.id for n in nodes if not n.ready()]}


def placements_per_node(snap, job_ids: set, index_of: dict) -> tuple:
    """Per-node count of the live placements of `job_ids`, and per job
    the count on each node index (for spread jobs only the caller
    looks). Blocks as columns, rows one by one."""
    store = snap._store
    counts = np.zeros(len(index_of), np.int64)
    per_job: dict = {}
    for block in snap.alloc_blocks():
        if block.job_id not in job_ids:
            continue
        for m in block.live_rows():
            i = index_of.get(block.node_ids[m])
            if i is not None:
                counts[i] += int(block.counts[m])
    for _, a in store._allocs.iterate(snap.index):
        if a.job_id in job_ids and not a.terminal_status():
            i = index_of.get(a.node_id)
            if i is not None:
                counts[i] += 1
                per_job.setdefault(a.job_id, []).append(i)
    return counts, per_job


def end_state(server, specs: list, complete: set, used0: np.ndarray,
              rules: dict, seed: int, solver_delta: dict,
              errors: list, attribute: str = "") -> dict:
    """Everything `judge` needs, as plain data. `specs` are the cell's
    job specs in submit order, `complete` the ids the client saw
    complete, `used0` the fleet's (cpu, mem) usage when the window
    opened (what the warm-up left behind included)."""
    import time

    t = [time.perf_counter()]
    took = {}

    def lap(name):
        t.append(time.perf_counter())
        took[name] = round(t[-1] - t[-2], 3)

    snap = server.store.snapshot()
    fleet = cluster_arrays(snap, attribute)
    lap("fleet")
    index_of = {nid: i for i, nid in enumerate(fleet["ids"])}
    done = [s for s in specs if s["id"] in complete]
    jobs = {s["id"]: {"count": int(s["count"]),
                      "live": live_count(snap, s["id"])} for s in done}
    lap("live_counts")
    rng = random.Random(f"sample-{seed}")
    k = min(int(rules.get("sample_nodes", 1024)), len(fleet["nodes"]))
    sample = [node_census(snap, n) for n in rng.sample(fleet["nodes"], k)]
    lap("node_census")
    counts, per_job = placements_per_node(snap, set(jobs), index_of)
    lap("placements")
    spread = {}
    for s in done:
        if s.get("spread"):
            rows = np.array(per_job.get(s["id"], []), np.int64)
            spread[s["id"]] = np.bincount(
                fleet["value_of"][rows],
                minlength=len(fleet["values"])).tolist()
    return {
        "took": took,
        "jobs": jobs, "sample": sample, "not_ready": fleet["not_ready"],
        "spread": spread,
        "fitness": mean_fitness(fleet["cap"], fleet["used"], counts),
        "retraces": int(solver_delta.get("retraces", 0)),
        "twin_failures": int(solver_delta.get("twin_failures", 0)),
        "errors": list(errors),
        # what the reference needs, as plain data: it runs after the
        # deployment is stopped, when nothing else wants the interpreter
        "_reference_input": (fleet["cap"], used0, fleet["value_of"],
                             len(fleet["values"]), done),
    }


def add_reference(state: dict, memo: dict = None) -> dict:
    """Run the plain reference on the same cluster and jobs and put its
    fitness beside the system's. The reference is a pure function of
    its inputs: rounds that start from the same usage with the same
    jobs (ids apart) share one run of it through `memo`."""
    import time

    t0 = time.perf_counter()
    cap, used0, value_of, n_values, jobs = state.pop("_reference_input")
    key = (cap.tobytes(), used0.tobytes(), value_of.tobytes(), n_values,
           repr([sorted((k, v) for k, v in job.items() if k != "id")
                 for job in jobs]))
    ref = None if memo is None else memo.get(key)
    if ref is None:
        ref = reference_fitness(cap, used0, value_of, n_values, jobs)
        if memo is not None:
            memo[key] = ref
    state["reference_fitness"] = ref["fitness"]
    state["reference_unplaced"] = ref["unplaced"]
    state["took"]["reference"] = round(time.perf_counter() - t0, 3)
    return state


def judge(state: dict, rules: dict) -> dict:
    """-> {"correct": bool, "reasons": [...], "failed_jobs": [ids],
    "compared": {name: [number, limit]}}: every number that is held,
    beside what it is held to. A count fails above 0; the evenness and
    the fitness gap fail above their limit."""
    reasons, failed_jobs = [], []
    for job_id, row in state["jobs"].items():
        if row["live"] != row["count"]:
            failed_jobs.append(job_id)
    compared = {"jobs_off_count": [len(failed_jobs), 0]}
    if failed_jobs:
        reasons.append(f"{len(failed_jobs)} complete job(s) without exactly "
                       f"`count` live allocations, e.g. {failed_jobs[:3]}")
    over = [n["id"] for n in state["sample"]
            if (np.asarray(n["used"]) > np.asarray(n["cap"]) + 1e-6).any()]
    compared["nodes_over_capacity"] = [len(over), 0]
    if over:
        reasons.append(f"nodes over capacity: {over[:3]}")
    clash = [n["id"] for n in state["sample"]
             if len(set(n["ports"])) != len(n["ports"])]
    compared["ports_twice"] = [len(clash), 0]
    if clash:
        reasons.append(f"port handed out twice on: {clash[:3]}")
    compared["nodes_down"] = [len(state["not_ready"]), 0]
    if state["not_ready"]:
        reasons.append(f"nodes down: {state['not_ready'][:3]}")
    rel = float(rules.get("spread_rel_tol", 0.0))
    slack = float(rules.get("spread_abs_tol", 1))
    # per spread job (max - min, its limit); the line shows the job
    # that comes nearest to its limit or passes it farthest
    evenness = {j: (max(per) - min(per), slack + rel * sum(per) / len(per))
                for j, per in state["spread"].items()}
    if evenness:
        compared["spread_max_less_min"] = list(max(
            evenness.values(), key=lambda e: e[0] - e[1]))
    uneven = [j for j, (got, limit) in evenness.items() if got > limit]
    if uneven:
        reasons.append(f"spread broken (max - min > {slack} + {rel} x mean) "
                       f"in {[(j, state['spread'][j]) for j in uneven[:3]]}")
        failed_jobs.extend(j for j in uneven if j not in failed_jobs)
    tol = float(rules.get("fitness_rel_tol", 5e-3))
    ref = state["reference_fitness"]
    compared["fitness_under_reference"] = [
        (ref - state["fitness"]) / ref if ref else 0.0, tol]
    if state["fitness"] < ref * (1.0 - tol):
        reasons.append(f"mean fitness {state['fitness']:.5f} below the "
                       f"reference's {ref:.5f}")
    compared["retraces"] = [state["retraces"], 0]
    compared["twin_failures"] = [state["twin_failures"], 0]
    if state["retraces"] or state["twin_failures"]:
        reasons.append(f"solver retraces={state['retraces']} "
                       f"twin_failures={state['twin_failures']}")
    compared["errors"] = [len(state["errors"]), 0]
    if state["errors"]:
        reasons.append(f"errors off the main thread: {state['errors'][:3]}")
    return {"correct": not reasons, "reasons": reasons,
            "failed_jobs": failed_jobs, "compared": compared}


def judge_rounds(states: list, rules: dict) -> dict:
    """`judge` over the end states of a run's rounds, as one verdict:
    correct only if every round is; of each number compared, the
    rounds' sum where it is a count held to 0, else the round that
    comes nearest its limit or passes it farthest."""
    verdicts = [judge(state, rules) for state in states]
    compared: dict = {}
    for v in verdicts:
        for name, (value, limit) in v["compared"].items():
            if name not in compared:
                compared[name] = [value, limit]
            elif limit == 0 and compared[name][1] == 0:
                compared[name][0] += value
            elif value - limit > compared[name][0] - compared[name][1]:
                compared[name] = [value, limit]
    many = len(verdicts) > 1
    return {"correct": all(v["correct"] for v in verdicts),
            "reasons": [f"round {k}: {r}" if many else r
                        for k, v in enumerate(verdicts)
                        for r in v["reasons"]],
            "failed_jobs": [j for v in verdicts for j in v["failed_jobs"]],
            "compared": compared}
